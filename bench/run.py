"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: whether
every result agreed with the reference (``correct``), lanes attempted
and failed in the window, the cell's end-to-end metrics (``--trace 0``)
or its per-layer metrics read from a profiled window (``--trace 1``),
the device, and the compared numbers with their limits (``checks``).
Exits non-zero, with no such line, where JAX finds no TPU or fewer
chips than the cell asks for.  See ``bench/harness.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # The compile cache lives at a fixed path inside the checkout, even
    # where the machine names a shared one, so that only a checkout's
    # first run compiles and two checkouts share nothing; every program
    # is kept, however quick to build.  Set before JAX starts;
    # ``place_compile_cache`` takes it from there.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    import jax

    from bench import harness
    from repro.core import simjax

    spec = harness.load_spec(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        print(f"bench: the cell needs {spec['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    print(f"compile cache: {simjax.place_compile_cache()}", file=sys.stderr)
    line = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                            T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
