#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this machine holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); everything else goes to standard error.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics.  Exits non-zero, with no result, when JAX finds no TPU, fewer
chips than the cell asks for, or a chip that is not in the peaks table:
there is no CPU mode.
"""

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    # Everything the run writes stays inside the checkout.
    os.chdir(REPO)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark import harness  # imports rayfed_tpu: fails outside the repo

    cell = harness.load_cell(args.workload)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # Party threads are daemons; leave through os._exit only after the
    # result is out, so a lingering transport thread cannot hold the
    # process (and the chip) open.
    code = 1
    try:
        code = main()
    except Exception:  # noqa: BLE001 — no result line on any failure
        import traceback

        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
