"""Survey §5.3: layer-pipeline (GPipe) demo over 4 pipeline stages.

    PYTHONPATH=src python examples/pipeline_training.py

Runs an MLP forward through the microbatch pipeline schedule, verifies it
against the sequential computation, and prints the bubble fraction predicted
by the paper's latency analysis vs the schedule's actual idle slots.
"""
import subprocess
import sys

CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.pipeline import pipeline_forward, num_pipeline_rounds
from repro.core.costmodel import pipeline_bubble_fraction
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("stage",))
S, M, mb, dim = 4, 8, 16, 32
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, dim, dim)) * 0.3
b = jax.random.normal(jax.random.PRNGKey(1), (S, dim)) * 0.1

def stage_fn(p, x):
    # p arrives pre-sliced to this stage: w (dim, dim), b (dim,)
    return jnp.tanh(x @ p["w"] + p["b"])

x = jax.random.normal(jax.random.PRNGKey(2), (M, mb, dim))
out = pipeline_forward(stage_fn, {"w": W, "b": b}, x, mesh)

ref = x
for s in range(S):
    ref = jnp.tanh(ref @ W[s] + b[s])
err = float(jnp.max(jnp.abs(out - ref)))
print(f"pipeline output matches sequential: maxerr={err:.2e}")

rounds = num_pipeline_rounds(S, M)
bubble = pipeline_bubble_fraction(S, M)
print(f"stages={S} microbatches={M}: {rounds} rounds, "
      f"bubble={(rounds - M) / rounds:.3f} (paper model: {bubble:.3f})")
print("DONE")
"""


def main():
    from repro.launch.mesh import cpu_devices_env
    env = {**cpu_devices_env(4), "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", CODE], env=env, text=True,
                       capture_output=True, timeout=900)
    print(r.stdout)
    if r.returncode != 0 or "DONE" not in r.stdout:
        print(r.stderr[-2000:])
        sys.exit(1)


if __name__ == "__main__":
    main()
