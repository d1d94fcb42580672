"""Record the small trace that ``test_trace.py`` reduces.  Run it on a TPU
from the root of a checkout:

    python3 bench/tests/record_trace.py [out_dir]

It traces four closed-loop calls of a tiny program shaped like the
benchmark's: a ``feed`` program, then a ``call`` whose ``local_step`` scope
scans three matmuls and whose ``round_set`` scope runs a Pallas kernel,
with the benchmark's host spans around them.  It writes the trace and the
call's compiled HLO text to ``out_dir`` (``bench/tests/data/`` by default).
"""
import glob
import pathlib
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def call(x, w):
    with jax.named_scope("local_step"):
        x, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None, length=3)
    with jax.named_scope("round_set"):
        y = pl.pallas_call(
            _kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), name="probe_kernel"
        )(x)
        y = y / jnp.sum(y)
    return y


def feed(c):
    return jnp.eye(1024, dtype=jnp.float32) * (0.5 + 0.0 * c)


def main(out=DATA):
    feed_c = jax.jit(feed).lower(0.0).compile()
    call_c = jax.jit(call, donate_argnums=(0,)).lower(
        jnp.ones((1024, 1024)), jnp.ones((1024, 1024))
    ).compile()
    x = jnp.ones((1024, 1024), jnp.float32)
    x = call_c(x, feed_c(0.0))
    x.block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for c in range(4):
            with jax.profiler.TraceAnnotation("bench.select"):
                w = feed_c(float(c))
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                x = call_c(x, w)
            with jax.profiler.TraceAnnotation("bench.sync"):
                x.block_until_ready()
    jax.profiler.stop_trace()
    out = pathlib.Path(out)
    out.mkdir(parents=True, exist_ok=True)
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(path, out / "trace.xplane.pb")
    (out / "call.hlo.txt").write_text(call_c.as_text())
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(*sys.argv[1:])
