"""Record a small profiler trace on this machine's chips, for the tests of
chipbench/trace_reduce.py: a few steps of a sharded matmul whose result
is summed across every device (a collective where there are several),
inside a `chipbench.window` annotation.  Copies the .xplane.pb to --out
and prints the trace's planes and lines.

  python3 chipbench/tools/record_trace.py --out trace.xplane.pb
"""
import argparse
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness, trace_reduce  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jax.device_put(jnp.ones((n * 512, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("d")))
    w = jnp.ones((1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        return y, jnp.sum(y.astype(jnp.float32), axis=0)   # cross-device

    jax.block_until_ready(step(x, w))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp,
                                 profiler_options=harness.profile_options())
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            for _ in range(3):
                y, s = step(x, w)
                jax.block_until_ready((y, s))
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(tmp)
        shutil.copy(path, args.out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(trace_reduce.describe(args.out))
    red = trace_reduce.reduce(trace_reduce.load(args.out))
    print(red)
    return 0


if __name__ == "__main__":
    sys.exit(main())
