"""One module a runner: ``Runner(ctx)`` with ``setup()``, ``window(seconds,
t_start)`` and ``finish()``. A cell's ``runner`` names the module."""
