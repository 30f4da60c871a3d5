"""Record a small slice of a traced run for test_trace_reduction.py.

After ``python3 bench/run.py --workload <cell> ... --trace 1`` on the
chip, the profiler trace of the window is under
``bench/.cache/trace/<cell>``.  This keeps ``seconds`` of it (device
operations and ``bench.*`` annotations), centred on a Pallas kernel
call where there is one, as JSON:

    python3 bench/tests/record_trace.py <cell> <out.json> [seconds]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import harness, trace  # noqa: E402


def main(cell, out, seconds=2.0):
    events = trace.load_xspace(os.path.join(harness.CACHE, "trace", cell))
    evs = [ev for v in events["device"].values() for ev in v]
    kernel = [s for _, s, _, text in evs if text]
    # centred on a Pallas kernel call where the window has one
    mid = kernel[len(kernel) // 2] if kernel else (
        min(ev[1] for ev in evs) + max(ev[1] for ev in evs)) / 2
    lo, hi = mid - seconds * 5e8, mid + seconds * 5e8
    rec = {"cell": cell, "lo": lo, "hi": hi,
           "events": trace.cut(events, lo, hi)}
    with open(out, "w") as f:
        json.dump(rec, f)
    n = sum(len(v) for v in rec["events"]["device"].values())
    print(json.dumps({"out": out, "device_events": n,
                      "bytes": os.path.getsize(out)}))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *map(float, sys.argv[3:]))
