"""Quickstart: the paper's pipeline end to end in ~60 seconds on CPU.

1. Encode/decode fabric messages (Fig. 1B) — bit-exact vs the paper.
2. Run the Fig. 2 programmability example on the fabric simulator.
3. Matrix-vector multiply with the Fig. 3 schedule (N+3 steps).
4. PageRank a small protein network on all three tiers and cross-check.
5. The paper's headline number from the analytical model (213.6 ms).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isa, schedule, timing
from repro.core.isa import Message
from repro.graph import generators as gen
from repro.graph import transition as tr
from repro.kernels import ops
from repro.pagerank import pagerank_dense_fixed, pagerank_on_fabric
from repro.serve.engine import top_k_proteins

print("=" * 64)
print("1. 64-bit message codec (Fig. 1B) — paper's Fig. 5 values")
print("=" * 64)
for hx in ["00f44121999a0051", "00d7404000000091"]:
    m = isa.from_hex(hx)
    print(f"  0x{hx} -> {isa.describe(m)}")
m = Message.make(isa.PROG, 5, 10.1, isa.A_ADD, 15)
assert isa.to_hex(m) == "00f44121999a0051"
print("  round-trip exact: OK")

print()
print("=" * 64)
print("2. Fig. 3 MV schedule on the fabric simulator")
print("=" * 64)
A = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
b = jnp.array([1.0, 2.0, 3.0])
res = schedule.matvec(A, b, use_messages=True)
print(f"  A@b = {np.asarray(res.result)}  (steps = {int(res.steps)} = N+3)")
assert int(res.steps) == 7

print()
print("=" * 64)
print("3. PageRank on a 60-protein network — three tiers")
print("   (60x61 = 3660 sites fits the 4096-site fabric whole; larger")
print("    networks use the Fig. 4C tiled schedule, step 4)")
print("=" * 64)
n = 60
src, dst = gen.protein_network(n, seed=0)
H = tr.build_transition_dense(src, dst, n)

pr_native = pagerank_dense_fixed(H, n_iters=50)
pr_fabric, steps, secs = pagerank_on_fabric(H, n_iters=50)
pr_kernel = jnp.full((n,), 1.0 / n)
for _ in range(50):
    pr_kernel = ops.pagerank_iteration(H, pr_kernel)

np.testing.assert_allclose(np.asarray(pr_native), np.asarray(pr_fabric),
                           rtol=1e-4)
np.testing.assert_allclose(np.asarray(pr_native), np.asarray(pr_kernel),
                           rtol=1e-4)
idx, scores = top_k_proteins(pr_native, k=5)
print(f"  native JAX == fabric simulator == fused Pallas kernel: OK")
print(f"  fabric steps: {steps} (= 50 x (N+6)); "
      f"@200MHz: {secs * 1e3:.3f} ms")
print(f"  top-5 proteins: {[int(i) for i in idx]}")

print()
print("=" * 64)
print("4. Fig. 4C tiled schedule on a 150-protein network (> one fabric)")
print("=" * 64)
n2 = 150
src2, dst2 = gen.protein_network(n2, seed=1)
H2 = tr.build_transition_dense(src2, dst2, n2)
tiled = schedule.pagerank_tiled(H2, n_iters=20)
ref2 = pagerank_dense_fixed(H2, n_iters=20)
np.testing.assert_allclose(np.asarray(tiled.result), np.asarray(ref2),
                           rtol=1e-4, atol=1e-7)
exp_steps = 20 * timing.pagerank_tiles(n2) * (64 + 6)
assert int(tiled.steps) == exp_steps
print(f"  tiled result == dense reference: OK "
      f"({int(tiled.steps)} steps = 20 iters x {timing.pagerank_tiles(n2)}"
      f" tiles x 70)")

print()
print("=" * 64)
print("5. The paper's headline (Fig. 6B)")
print("=" * 64)
t = timing.pagerank_latency_s(5000, 100)
print(f"  5000 proteins, 100 iterations, 4096 sites @ 200 MHz: "
      f"{t * 1e3:.2f} ms  (paper: 213.6 ms)")
print("\nquickstart: ALL OK")
