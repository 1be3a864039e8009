"""Device trace -> numbers: the ``.xplane.pb`` reader and the reductions."""
