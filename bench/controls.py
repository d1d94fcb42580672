"""Read the numbers that decide ``correct`` for one cell over many seeds:
the program's, the control's (the reference one precision below the
configuration's, in the program's place) and each planted fault's.  The
limits in ``bench/limits/<cell>.json`` are set from these readings.  Run
it on the cell's chips, from the root of a checkout:

    python3 bench/controls.py --workload <cell> --seeds 12 --out <file.json>

No measured window: each seed runs the timed call through its first calls
and the reference follows them, as in a run.  The control and each fault
are read on the first ``OTHER_SEEDS`` seeds.  A call that returns its state
unchanged reads 1 on the momentum and the change by construction, so it is
not run here; the CPU tests plant it.
"""
import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from harness import check, spec  # noqa: E402
from harness import reference as ref_mod  # noqa: E402
from harness.faults import FAULTS  # noqa: E402
from harness.run import build, enable_compile_cache, first_batches, reference_run, start  # noqa: E402

SEED0 = 2_000_000_000
OTHER_SEEDS = 3
CHIP_FAULTS = ("half_batch", "no_exchange")


def readings(cell, seeds, control_seeds=0, fault=None) -> dict:
    """Per seed, the program's numbers (with ``fault`` planted, where
    given), and for the first ``control_seeds`` seeds the control's."""
    p = build(cell, FAULTS[fault] if fault else None)
    out = {"program": {}, "control": {}}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        s = start(p, seed)
        batches = first_batches(p, s)
        ref = reference_run(p, s, batches)
        out["program"][seed] = check.numbers(s.prog, ref)
        if i < control_seeds:
            ctrl = reference_run(p, s, batches, ref_mod.control_policy(cell.config))
            out["control"][seed] = check.numbers(ctrl, ref)
        print(f"{cell.name} {fault or 'sound'} seed {seed}: {out['program'][seed]} "
              f"control {out['control'].get(seed)} ({time.perf_counter() - t:.1f} s)",
              file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=SEED0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # as bench/run.py
    import jax

    if jax.devices()[0].platform != "tpu":
        print("controls: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = spec.find_cell(args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    result = {"workload": cell.name, "device": jax.devices()[0].device_kind}
    result.update(readings(cell, seeds, OTHER_SEEDS))
    result["faults"] = {
        fault: readings(cell, seeds[:OTHER_SEEDS], fault=fault)["program"] for fault in CHIP_FAULTS
    }
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(result, indent=1))
    for kind in ("program", "control"):
        for k in check.NUMBERS:
            vals = [v[k] for v in result[kind].values()]
            if vals:
                print(f"{kind} {k}: max {max(vals)!r} min {min(vals)!r}")
    for fault, per_seed in result["faults"].items():
        for k in check.NUMBERS:
            vals = [v[k] for v in per_seed.values()]
            print(f"fault {fault} {k}: min {min(vals)!r} max {max(vals)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
