"""The toy manifest of the CPU tests, derived and never written by hand.

``end_to_end`` and ``per_layer`` are ``BENCHMARK.json``'s own entries;
configurations and cells are the files under ``toy/configs`` and
``toy/cells``. A toy cell file names, under ``"toy"``, its configuration, its
traffic, its chips and the committed cell it ``stands_for``: it reports what
that cell reports. So a new per-layer metric, a new toy cell or a new toy
configuration is a new file (and, for a metric, its entry in
``BENCHMARK.json``), and nothing here or in the tests is edited for it.

``toy/MANIFEST.json`` is not this manifest: it names the one toy cell that
a test outside the benchmark's directories hands to the program's tool
(``tools/ds_hlo_scopes.py --manifest``), and goes when that test may be
pointed at ``manifest_path()``.
"""

import atexit
import functools
import glob
import json
import os
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY_DIR = os.path.join("tests", "benchmark", "toy")


def _load(path):
    with open(path) as fh:
        return json.load(fh)


def _bodies(folder):
    paths = sorted(glob.glob(os.path.join(ROOT, TOY_DIR, folder, "*.json")))
    return {os.path.splitext(os.path.basename(p))[0]: _load(p) for p in paths}


def cells():
    """{toy cell's name: its file's ``"toy"`` group}, in the files' order."""
    return {name: body["toy"] for name, body in _bodies("cells").items()}


def manifest():
    real = _load(os.path.join(ROOT, "BENCHMARK.json"))
    toy_cells = cells()

    def metric(m):
        if "workloads" not in m:
            return dict(m)
        return dict(m, workloads=[name for name, t in toy_cells.items()
                                  if t["stands_for"] in m["workloads"]])

    return {
        "command": real["command"],
        "paths": [TOY_DIR, "benchmark"],
        "run_seconds": 2,
        "configs": [dict(name=name, source="none", file=f"{TOY_DIR}/configs/{name}.json",
                         reduced=[], why="toy") for name in _bodies("configs")],
        "workloads": [dict(name=name, config=t["config"], traffic=t["traffic"],
                           chips=t["chips"], why="toy") for name, t in toy_cells.items()],
        "end_to_end": [metric(m) for m in real["end_to_end"]],
        "per_layer": [metric(m) for m in real["per_layer"]],
    }


@functools.cache
def manifest_path():
    """The derived manifest as a file (``harness.run_cell`` takes a path);
    written once a process, removed when it exits."""
    fd, path = tempfile.mkstemp(prefix="toy_manifest_", suffix=".json")
    with os.fdopen(fd, "w") as fh:
        json.dump(manifest(), fh)
    atexit.register(os.remove, path)
    return path
