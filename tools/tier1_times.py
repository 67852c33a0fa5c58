#!/usr/bin/env python
"""Where tier-1's time goes, from the junit the driver's command writes.

    python tools/tier1_times.py /tmp/_t1.xml

Prints the wall time, the sum of the cases' times (the work: ``--dist
loadfile`` pins a file to one worker, so a file's sum is a floor on the wall),
the sum by file and the cases of 10 s or more. No option but the path.
"""
import sys
import xml.etree.ElementTree as ET
from collections import defaultdict

LARGE_S = 10.0


def report(path: str) -> str:
    suite = ET.parse(path).getroot().find("testsuite")
    cases = [(c.get("classname"), c.get("name"), float(c.get("time")))
             for c in suite.iter("testcase")]
    by_file = defaultdict(lambda: [0.0, 0])
    for classname, _, seconds in cases:
        # "tests.unit.test_x.TestClass" -> "tests.unit.test_x"
        parts = classname.split(".")
        while parts and not parts[-1].startswith("test_"):
            parts.pop()
        entry = by_file[".".join(parts) or classname]
        entry[0] += seconds
        entry[1] += 1
    large = sorted((c for c in cases if c[2] >= LARGE_S), key=lambda c: -c[2])
    lines = [f"wall {float(suite.get('time')):.0f} s, "
             f"sum {sum(c[2] for c in cases):.0f} s, {len(cases)} cases, "
             f"{len(large)} of {LARGE_S:.0f} s or more "
             f"({sum(c[2] for c in large):.0f} s)",
             "", "by file (seconds, cases):"]
    lines += [f"{seconds:8.1f} {count:5d}  {name}" for name, (seconds, count)
              in sorted(by_file.items(), key=lambda kv: -kv[1][0])]
    lines += ["", f"cases of {LARGE_S:.0f} s or more:"]
    lines += [f"{seconds:8.1f}  {classname}::{name}"
              for classname, name, seconds in large]
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    print(report(sys.argv[1]))
