"""One run of one cell: set-up, the measured window, the check.

Set-up builds the trainer, compiles the feed and the timed call, makes the
weights and data on the device from the seed, and drives the timed call
itself through its first ``CHECK_CALLS`` calls, which the reference
follows.  The window then drives the same call, closed loop, for
``--seconds``: one client dispatches the next call when the last one's loss
is back.  After the window the peak memory is read, the program's state is
freed, and the reference follows the first calls from the same weights and
batches.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from harness import check, peaks, spec
from harness import reference as ref_mod
from harness.driver import make_driver
from harness.program import build_trainer, make_state_fn

CHECK_CALLS = 3


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    """JAX's persistent cache, at ``JAX_COMPILATION_CACHE_DIR`` where that
    is set and otherwise at a fixed path inside the checkout; every program
    is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def root_key(seed: int):
    """A key from any whole number, also one past 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


@dataclasses.dataclass
class Program:
    """The cell's system under test, compiled once for any seed."""

    cell: spec.Cell
    trainer: object
    init: object  # key -> one agent's weights
    state_fn: object  # key -> DecentralizedState (jitted)
    data_fn: object | None  # key -> staged data (jitted), or None
    feed: object  # (data, root, c) -> (batches, keys) of call c (compiled)
    driver: object  # (state, batches, keys) -> (state, losses) (compiled)


def build(cell: spec.Cell, fault=None) -> Program:
    """``fault(trainer, telemetry)``, where given, makes the call's
    un-jitted body in place of the driver's (a planted fault)."""
    import jax

    cfg, model = cell.config, cell.model
    trainer = build_trainer(cell)
    init = model.init_params(cfg)
    state_fn = make_state_fn(trainer, init)
    data_fn = jax.jit(model.make_data(cfg)) if hasattr(model, "make_data") else None
    select = model.select(cfg, cell.traffic)
    L = cell.traffic["local_steps"]

    def feed(data, root, c):
        kb, ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(root, 2), c))
        return select(data, kb), jax.random.split(ks, L)

    key = jax.random.key(0)
    data = jax.eval_shape(data_fn, key) if data_fn else None
    args = (data, key, np.int32(0))
    compiled_feed = jax.jit(feed).lower(*args).compile()
    telemetry = cell.traffic["telemetry"]
    body = fault(trainer, telemetry) if fault else None
    driver = make_driver(trainer, telemetry, body).lower(
        jax.eval_shape(state_fn, key), *jax.eval_shape(feed, *args)
    ).compile()
    return Program(cell, trainer, init, state_fn, data_fn, compiled_feed, driver)


@dataclasses.dataclass
class Started:
    """One seed's state after its first calls, and what the check needs."""

    state: object
    data: object
    root: object
    wkey: object
    prog: dict  # losses, momentum and change norms of the first calls


def start(p: Program, seed: int) -> Started:
    """Weights and data from the seed, then the first calls through the
    timed call itself."""
    import jax

    root = root_key(seed)
    wkey = jax.random.fold_in(root, 0)
    state = p.state_fn(wkey)
    data = p.data_fn(jax.random.fold_in(root, 1)) if p.data_fn else None
    prog = {"losses": []}
    for c in range(CHECK_CALLS):
        state, losses = p.driver(state, *p.feed(data, root, np.int32(c)))
        prog["losses"].append(np.asarray(losses))
        if c == 0:
            prog["momentum"] = ref_mod.leaf_norms(state.opt_state["m"])
    p0 = jax.jit(p.init)(wkey)
    prog["change"] = ref_mod.change_norms(state.params, p0)
    return Started(state, data, root, wkey, prog)


def window(p: Program, s: Started, seconds: float) -> dict:
    """Closed-loop calls until ``seconds`` have passed; ``s.state`` moves on."""
    import jax

    times, failed, c = [], 0, CHECK_CALLS
    with jax.profiler.TraceAnnotation("bench.window"):
        begin = time.perf_counter()
        while True:
            t_a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.select"):
                batches, keys = p.feed(s.data, s.root, np.int32(c))
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                s.state, losses = p.driver(s.state, batches, keys)
            with jax.profiler.TraceAnnotation("bench.sync"):
                losses = np.asarray(losses)
            t_b = time.perf_counter()
            times.append(t_b - t_a)
            failed += not np.all(np.isfinite(losses))
            c += 1
            if t_b - begin >= seconds:
                break
    return {"times": times, "failed": failed, "window_s": t_b - begin}


def first_batches(p: Program, s: Started) -> list:
    """The batches of the first calls, made again from the seed; frees the
    program's state, so that the reference fits beside what is left."""
    batches = [p.feed(s.data, s.root, np.int32(c))[0] for c in range(CHECK_CALLS)]
    s.state = s.data = None
    return batches


def reference_run(p: Program, s: Started, batches, policy=ref_mod.REFERENCE) -> dict:
    """The reference (or, with a lower ``policy``, the control) over the
    first calls' batches, from the same weights."""
    import jax

    p0 = jax.jit(p.init)(s.wkey)
    ref = ref_mod.train(p.cell.reference.loss, p.cell.config, p.cell.traffic, p0, batches, policy)
    ref["change"] = ref_mod.change_norms(ref.pop("params"), p0)
    return ref


def counts(cell: spec.Cell) -> dict:
    """What the per-layer readers divide by: the work of one call."""
    import jax

    cfg, traffic, model = cell.config, cell.traffic, cell.model
    p0 = jax.eval_shape(model.init_params(cfg), jax.random.key(0))
    return {
        "K": cfg["num_agents"],
        "D": sum(math.prod(x.shape) for x in jax.tree.leaves(p0)),
        "model_flops_per_call": model.samples_per_call(cfg, traffic) * model.flops_per_sample(cfg),
    }


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, t0: float, fault=None) -> dict:
    """The result line of one run (``t0``: when the process started)."""
    import jax

    t_build = time.perf_counter()
    p = build(cell, fault)
    t_start = time.perf_counter()
    s = start(p, seed)
    setup_s = time.perf_counter() - t0
    print(f"setup: {t_build - t0:.3f} s to build, {t_start - t_build:.3f} s to compile, "
          f"{setup_s - (t_start - t0):.3f} s for weights, data and the first calls",
          file=sys.stderr)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    w = window(p, s, seconds)
    if trace:
        jax.profiler.stop_trace()
    device = jax.devices()[0]
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    t_ref = time.perf_counter()
    ref = reference_run(p, s, first_batches(p, s))
    print(f"reference: {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    values = check.numbers(s.prog, ref)
    finite = all(np.all(np.isfinite(x)) for x in s.prog["losses"])
    n_calls = len(w["times"])
    result = {
        "correct": check.judge(values, cell.limits) and w["failed"] == 0 and finite,
        "attempted": n_calls,
        "failed": w["failed"],
        "metrics": {},
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }
    if trace:
        from harness import trace as trace_mod

        reading = trace_mod.read(trace_dir, [p.driver.as_text()], n_calls, w["window_s"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        reading.counts, reading.device_kind = counts(cell), device.device_kind
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"]).read(reading)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"].update(busy_s=reading.busy_s, window_s=reading.window_s)
        result["breakdown"] = reading.breakdown()
    else:
        e2e = {
            "samples_per_s": n_calls * cell.model.samples_per_call(cell.config, cell.traffic) / w["window_s"],
            "call_ms_p95": float(np.percentile(np.asarray(w["times"]) * 1e3, 95)),
            "peak_hbm_gib": peak / 2**30 if peak is not None else None,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result["checks"] = {k: {"value": values[k], "limit": cell.limits[k]} for k in check.NUMBERS}
    return result


def report(result: dict) -> None:
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(f"check correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    cell = spec.find_cell(args.workload)
    # the TPU runtime logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX sees "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 1
    peaks.lookup(devices[0].device_kind)  # an unknown chip is an error
    enable_compile_cache()
    report(run(cell, args.seed, args.seconds, bool(args.trace), t0))
    return 0
