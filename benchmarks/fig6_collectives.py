"""Paper Fig 6 / §2.5: allreduce algorithm comparison.

Two views:
  (a) analytical α-β model times on TPU-v5e link constants across message
      sizes — reproducing the paper's regime analysis (butterfly for small
      γm, ring/rabenseifner for large), and
  (b) measured wall time of our shard_map schedules on 8 virtual CPU devices
      (spawned subprocess pinned to the CPU — this process stays
      single-device). A failed child fails this module.
"""
import json
import subprocess
import sys
import textwrap

from benchmarks.common import emit
from repro.core import costmodel as cm
from repro.launch.mesh import cpu_devices_env

L, G = 1e-6, 1.0 / 50e9   # ICI-ish constants


def analytical():
    for m in (4_096, 1_048_576, 268_435_456):      # elements
        times = {
            "tree": cm.t_tree(256, m, L, G),
            "butterfly": cm.t_butterfly(256, m, L, G),
            "ring": cm.t_pipeline(256, m, L, G),
            "rabenseifner": cm.t_rabenseifner(256, m, L, G),
        }
        best = min(times, key=times.get)
        lb = cm.t_lower_bound(256, m, L, G)
        for alg, t in times.items():
            emit(f"fig6/analytical/m={m}/{alg}", t * 1e6,
                 f"vs_lower_bound={t/lb:.2f} best={alg == best}")


def measured():
    code = textwrap.dedent("""
        import json, time
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import collectives as coll
        mesh = make_mesh((8,), ('x',))
        out = {}
        x = jnp.ones((8, 262144), jnp.float32)
        for alg in coll.ALGORITHMS:
            f = jax.jit(shard_map(
                lambda v: coll.allreduce_sum(v[0], 'x', algorithm=alg)[None],
                mesh=mesh, in_specs=P('x'), out_specs=P('x'), check_vma=False))
            jax.block_until_ready(f(x))
            t0 = time.perf_counter()
            for _ in range(5):
                jax.block_until_ready(f(x))
            out[alg] = (time.perf_counter() - t0) / 5 * 1e6
        print('RESULT ' + json.dumps(out))
    """)
    env = {**cpu_devices_env(8), "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    for line in r.stdout.splitlines():
        if r.returncode == 0 and line.startswith("RESULT "):
            for alg, us in json.loads(line[7:]).items():
                emit(f"fig6/measured_8dev_1M/{alg}", us, "host-CPU emulation")
            return
    raise RuntimeError(f"fig6 8-device child failed (rc={r.returncode}):\n"
                       f"{r.stderr[-2000:]}")


def main():
    analytical()
    measured()


if __name__ == "__main__":
    main()
