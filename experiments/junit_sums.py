"""What the tier-1 run spent, from its junit file: the tests' own seconds
summed by file (and the slowest tests), the numbers ROADMAP D3's budget is
stated in.

    python experiments/junit_sums.py /tmp/_t1.xml [N slowest tests, default 30]

The sum over six workers is what decides whether the run ends inside the
command's limit (sum / 6, plus what the last files leave idle); a file's line
says what a PR's own tests cost.
"""

import collections
import sys
import xml.etree.ElementTree as ET


def main(path: str, slowest: int = 30) -> None:
    by_file, tests = collections.defaultdict(lambda: [0, 0.0]), []
    for case in ET.parse(path).getroot().iter("testcase"):
        name = ".".join(part for part in case.get("classname").split(".") if not part.startswith("Test"))
        seconds = float(case.get("time"))
        by_file[name][0] += 1
        by_file[name][1] += seconds
        tests.append((seconds, f"{name}::{case.get('name')}"))
    total = sum(seconds for _, seconds in by_file.values())
    print(f"{total:9.1f} s in {len(tests)} tests, {total / 6:.1f} s a worker of six")
    for name, (count, seconds) in sorted(by_file.items(), key=lambda kv: -kv[1][1]):
        print(f"{seconds:9.1f} {count:5d} {name}")
    print()
    for seconds, name in sorted(tests, reverse=True)[:slowest]:
        print(f"{seconds:9.1f} {name}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 30)
