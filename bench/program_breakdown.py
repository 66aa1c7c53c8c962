#!/usr/bin/env python3
"""Print the program's spans in the last traced run's profile.

    python bench/program_breakdown.py [--trace-dir DIR]

After ``bench/run.py --trace 1`` the profile is in ``bench/.out/trace``;
this prints one JSON line of `lib/program_trace.reduce_trace`: seconds and
count of each ``repro.*`` span in the window, the device's idle time under
each harness phase split by the innermost program span
(``<phase>/<span>``), and the inflight steps in the window and inside
``finish``.  It reads the trace and touches no device.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib import program_trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-dir", default=None,
                    help=f"default {program_trace.TRACE_DIR}")
    args = ap.parse_args(argv)
    red = program_trace.reduce_trace(args.trace_dir)
    if not red:
        print(f"no traced window in "
              f"{args.trace_dir or program_trace.TRACE_DIR}", file=sys.stderr)
        return 1
    print(json.dumps(red, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
