"""Record the small chip trace ``test_trace_reduce`` reads: three rounds,
each a ``round.gather`` annotation around a 2 ms host sleep and a
``round.server`` annotation around one ``batched_quantize`` launch and
one matmul, all inside a ``perf.window`` annotation.

Usage (on a TPU, from the repository root):
  python perf/tests/record_tiny_trace.py perf/testdata/tiny.xplane.pb
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from repro.kernels import ops
    if jax.devices()[0].platform != "tpu":
        print("record_tiny_trace: no TPU", file=sys.stderr)
        return 1
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    mm = jax.jit(lambda a: a @ a)

    def server():
        jax.block_until_ready((ops.batched_quantize(x, chunk=256), mm(w)))

    server()                                           # compile outside
    where = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(where, profiler_options=opts)
    with jax.profiler.TraceAnnotation("perf.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("round.gather"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("round.server"):
                server()
    jax.profiler.stop_trace()
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(next(Path(where).rglob("*.xplane.pb")), out)
    shutil.rmtree(where, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
