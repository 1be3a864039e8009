#!/usr/bin/env python3
"""Where a tier-1 run's time went, and the wall its schedule gives.

    python tools/suite_times.py /tmp/_t1.xml

Reads the junit file of the tier-1 command (ROADMAP.md) and prints seconds
and cases by directory and by file, then replays the files through
``-n 6 --dist loadfile`` as pytest-xdist 3.8 runs it: files queued by their
number of cases, largest first, the next file to the first worker that falls
free. The wall is the sum over six plus what the last file overhangs, so a
long file of few cases starts late and ends last. Run it before adding a test
file: the tier-1 command is cut at LIMIT seconds and counts only what ran.
"""
import heapq
import sys
import xml.etree.ElementTree as ET
from collections import defaultdict

WORKERS = 6
LIMIT = 1470  # the tier-1 command's `timeout`, seconds
# Before the first case runs, six workers start, import and collect the suite; after the
# last, they shut down. Measured wall less the replayed schedule: 27 s (PR 46, the driver's)
# and 58, 63, 82, 66 s (PR 47's four whole runs).
START_UP = 50


def by_file(junit_path):
    """{file: [seconds, cases]} of a junit file; a file is the dotted
    classname up to its first ``test_*`` part (a test class comes after)."""
    files = defaultdict(lambda: [0.0, 0])
    for case in ET.parse(junit_path).iter("testcase"):
        parts = case.get("classname", "").split(".")
        cut = next((i for i, p in enumerate(parts) if p.startswith("test_")), len(parts) - 1)
        entry = files["/".join(parts[: cut + 1]) + ".py"]
        entry[0] += float(case.get("time", 0))
        entry[1] += 1
    return dict(files)


def replay(files, workers=WORKERS):
    """(projected wall, the file that ends last, {file: (start, end) after START_UP})."""
    free = [(0.0, w) for w in range(workers)]  # seconds after START_UP
    spans = {}
    for name, (seconds, _) in sorted(files.items(), key=lambda kv: -kv[1][1]):
        start, w = heapq.heappop(free)
        spans[name] = (start, start + seconds)
        heapq.heappush(free, (start + seconds, w))
    last = max(spans, key=lambda n: spans[n][1])
    return START_UP + spans[last][1], last, spans


def main(argv):
    if len(argv) != 2:
        sys.exit(__doc__)
    files = by_file(argv[1])
    dirs = defaultdict(lambda: [0.0, 0])
    for name, (seconds, cases) in files.items():
        parts = name.split("/")
        entry = dirs["/".join(parts[: min(3, len(parts) - 1)])]
        entry[0] += seconds
        entry[1] += cases
    wall, last, spans = replay(files)
    total = sum(s for s, _ in files.values())
    print(f"{'seconds':>9} {'cases':>6}  directory")
    for name, (seconds, cases) in sorted(dirs.items(), key=lambda kv: -kv[1][0]):
        print(f"{seconds:9.1f} {cases:6d}  {name}")
    print(f"\n{'seconds':>9} {'cases':>6} {'start':>7} {'end':>7}  file")
    for name, (seconds, cases) in sorted(files.items(), key=lambda kv: -kv[1][0]):
        print(f"{seconds:9.1f} {cases:6d} {spans[name][0]:7.0f} {spans[name][1]:7.0f}  {name}")
    print(f"\n{total:.0f} s of test time in {sum(c for _, c in files.values())} cases of {len(files)} files")
    print(f"sum / {WORKERS} = {total / WORKERS:.0f} s; projected wall {wall:.0f} s "
          f"({100 * wall / LIMIT:.0f} % of the {LIMIT} s limit); ends last: {last} "
          f"({spans[last][0]:.0f} -> {spans[last][1]:.0f} s)")


if __name__ == "__main__":
    main(sys.argv)
