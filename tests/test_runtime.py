"""Heterogeneous execution engine (GHOST 4.1 + 4.2).

Host-side pieces (DevicePool, SplitPlan, rebalance convergence) run in the
main process; everything needing a multi-shard mesh runs in a 2-device
subprocess via conftest.run_with_devices.
"""
import numpy as np
import pytest

from conftest import run_with_devices

from repro.launch.costmodel import spmv_code_balance, spmv_cost
from repro.launch.hillclimb import proportional_step
from repro.runtime import DevicePool, plan_split


# ---------------------------------------------------------------- devicepool
class TestDevicePool:
    def test_detect_host(self):
        pool = DevicePool.detect()
        assert pool.ndevices >= 1
        assert len(pool.device_classes()) == pool.ndevices

    def test_v5e_kind_has_published_specs(self):
        from types import SimpleNamespace
        from repro.launch.mesh import HW
        chips = [SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
                 for _ in range(4)]
        pool = DevicePool.detect(chips)
        assert [(c.name, c.count) for c in pool.classes] == \
            [("TPU v5 lite", 4)]
        assert pool.classes[0].mem_bw == HW["hbm_bw"] == 819e9
        assert pool.classes[0].peak_flops == HW["peak_flops_bf16"] == 197e12
        assert np.allclose(pool.device_weights(), 0.25)

    @pytest.mark.parametrize("kind", ["TPU v5", "TPU v5p", "TPU v4",
                                      "NVIDIA A100-SXM4-40GB"])
    def test_unknown_accelerator_kind_raises(self, kind):
        from types import SimpleNamespace
        dev = SimpleNamespace(device_kind=kind, platform="tpu")
        with pytest.raises(ValueError, match="no published specs"):
            DevicePool.detect([dev])

    def test_synthetic_paper_node(self):
        """Paper Table 1: CPU 50 + GPU 150 + PHI 150 GB/s."""
        pool = DevicePool.from_bandwidths([50, 150, 150])
        w = pool.device_weights()
        assert np.allclose(w, [50 / 350, 150 / 350, 150 / 350])
        # min code balance 6 B/flop (f64 vals + i32 idx) -> 350/6 Gflop/s
        pred = pool.aggregate_spmv_gflops(nnzr=1e9)   # huge row amortizes y
        assert abs(pred - 350.0 / 6.0) < 1.0

    def test_code_balance_reference_point(self):
        assert spmv_code_balance(val_bytes=8, idx_bytes=4,
                                 nnzr=1e12) == pytest.approx(6.0)
        # block vectors amortize the matrix stream (paper's SpMMV argument)
        cb4 = spmv_code_balance(val_bytes=8, idx_bytes=4, nvecs=4, nnzr=1e12)
        assert cb4 < 6.0 / 2

    def test_roofline_time(self):
        pool = DevicePool.from_bandwidths([100])
        cost = spmv_cost(10_000, 100, val_bytes=4)
        t = pool.classes[0].time_for(cost)
        assert t == pytest.approx(cost.hbm_bytes / 100e9)


# ---------------------------------------------------------------- splitting
class TestSplitPlan:
    def test_split_sums_and_alignment(self):
        for n, align in [(1000, 32), (997, 8), (64, 32), (12345, 16)]:
            p = plan_split(n, [1, 2.75, 0.5], align=align)
            assert p.sizes.sum() == n
            starts = [s for s, _ in p.ranges]
            assert all(s % align == 0 for s in starts)
            # contiguous cover
            assert p.ranges[0][0] == 0 and p.ranges[-1][1] == n
            assert all(p.ranges[i][1] == p.ranges[i + 1][0]
                       for i in range(p.nshards - 1))

    def test_no_empty_shards_under_skew(self):
        p = plan_split(256, [1000.0, 1.0, 1.0, 1.0], align=32)
        assert (p.sizes > 0).all()
        assert p.sizes.sum() == 256

    def test_proportionality(self):
        p = plan_split(100_000, [1.0, 3.0], align=32)
        assert abs(p.sizes[1] / p.sizes[0] - 3.0) < 0.01

    def test_nnz_criterion(self):
        rowlen = np.concatenate([np.full(100, 50), np.full(900, 5)])
        p = plan_split(1000, [1, 1], align=4, rowlen=rowlen)
        nnz = p.shard_nnz()
        assert abs(nnz[0] - nnz[1]) / nnz.sum() < 0.1
        assert p.sizes.sum() == 1000

    def test_rebalance_one_step_moves_toward_measured(self):
        p = plan_split(10_000, [1.0, 1.0], align=8)
        # shard 0's device is 3x slower -> its time is 3x at equal rows
        p2 = p.rebalance([3.0, 1.0], step=1.0)
        assert p2.generation == 1
        assert p2.weights[0] < p2.weights[1]

    def test_rebalance_converges_on_skewed_pool(self):
        """Satellite criterion: weights converge toward the measured
        throughput ratio of a synthetic 1:3 pool."""
        speed = np.array([1.0, 3.0])
        p = plan_split(30_000, [1.0, 1.0], align=8)
        for _ in range(8):
            times = (p.sizes / p.sizes.sum()) / speed
            p = p.rebalance(times, step=0.7)
        w = np.asarray(p.weights)
        assert abs(w[1] / w[0] - 3.0) < 0.15, w
        # fixed point: per-shard times equalized
        times = (p.sizes / p.sizes.sum()) / speed
        assert p.imbalance(times) < 1.02

    def test_proportional_step_validates(self):
        with pytest.raises(ValueError):
            proportional_step([1.0, -1.0], [1.0, 1.0])


# ------------------------------------------------------------------- engine
class TestEngineSingleDevice:
    def test_spmv_matches_dense(self, rng):
        from repro.matrices import matpde
        from repro.runtime import HeterogeneousEngine
        r, c, v, n = matpde(16)
        A = np.zeros((n, n)); A[r, c] += v
        eng = HeterogeneousEngine(r, c, v, n, C=8, sigma=16, w_align=4,
                                  dtype=np.float32)
        x = rng.standard_normal((n, 2)).astype(np.float32)
        y, _ = eng.spmv(x)
        assert np.allclose(np.asarray(y), A @ x, atol=1e-3)

    def test_rebalance_keeps_correctness(self, rng):
        from repro.matrices import matpde
        from repro.runtime import HeterogeneousEngine
        r, c, v, n = matpde(12)
        A = np.zeros((n, n)); A[r, c] += v
        eng = HeterogeneousEngine(r, c, v, n, C=8, sigma=8, w_align=4,
                                  dtype=np.float32)
        eng.rebalance()          # modeled-times fallback path
        x = rng.standard_normal(n).astype(np.float32)
        y, _ = eng.spmv(x)
        assert np.allclose(np.asarray(y), A @ x, atol=1e-3)


CODE_TEMPLATE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.runtime import DevicePool, HeterogeneousEngine
from repro.core.spmv import SpmvOpts
from repro.matrices import banded_random, matpde

rng = np.random.default_rng(0)
mesh = Mesh(np.array(jax.devices()).reshape(2), ("data",))
pool = DevicePool.from_bandwidths([50, 150])
{body}
print("SUBPROCESS_OK")
"""


def run2(body: str) -> str:
    out = run_with_devices(CODE_TEMPLATE.format(body=body), 2)
    assert "SUBPROCESS_OK" in out
    return out


class TestEngineMultiShard:
    def test_engine_end_to_end_two_shards(self):
        """One subprocess (jax init is the dominant cost), five checks:

        1. acceptance: overlap=True == overlap=False bit-for-bit through
           the runtime, both matching spmv_ref on the 2-shard host mesh;
        2. the double-buffered halo chain is a pure re-schedule
           (bit-identical to the unbuffered chain);
        3. fused gamma-shift + dots through the engine;
        4. CG through DistOperator converges to the dense solution;
        5. the split follows the pool's 150/50 bandwidth ratio.
        """
        run2("""
from repro.core import from_coo
from repro.core.spmv import spmv_ref
from repro.solvers import cg, make_operator

# -- 1. overlap bit-identity + correctness ----------------------------------
r, c, v, n = banded_random(400, bw=8, density=0.6, seed=4)
A = np.zeros((n, n)); A[r, c] += v
eng = HeterogeneousEngine(r, c, v, n, mesh=mesh, pool=pool, C=8, sigma=16,
                          w_align=4, dtype=np.float32)
x = rng.standard_normal((n, 2)).astype(np.float32)
y1, _ = eng.spmv(x, overlap=True)
y2, _ = eng.spmv(x, overlap=False)
assert np.array_equal(np.asarray(y1), np.asarray(y2)), "overlap changed bits"
As = from_coo(r, c, v, (n, n), C=8, sigma=16, w_align=4, dtype=np.float32)
yr = As.unpermute(spmv_ref(As, As.permute(jnp.asarray(x)))[0])
assert np.allclose(np.asarray(y1), np.asarray(yr), atol=1e-4)
assert np.allclose(np.asarray(y1), A @ x, atol=1e-3)
print("CHECK overlap_bit_identical OK")

# -- 2. double-buffered chain == unbuffered chain ---------------------------
xs = eng.A.distribute_vec(x[:, :1])
run_db = eng.make_matvec(nvecs=1, double_buffer=True)
run_nb = eng.make_matvec(nvecs=1)
w, stg = xs, None
for _ in range(3):
    w, _, stg = run_db(w, staging=stg)
w2 = xs
for _ in range(3):
    w2, _, _ = run_nb(w2)
assert np.array_equal(np.asarray(w), np.asarray(w2))
print("CHECK double_buffer OK")

# -- 3. fused gamma + dots --------------------------------------------------
y, dots = eng.spmv(x, opts=SpmvOpts(alpha=2.0, gamma=0.5,
                                    dot_yy=True, dot_xx=True))
ref = 2.0 * (A @ x - 0.5 * x)
assert np.allclose(np.asarray(y), ref, atol=1e-3)
assert np.allclose(np.asarray(dots[0]), (ref * ref).sum(0), rtol=1e-3)
assert np.allclose(np.asarray(dots[2]), (x * x).sum(0), rtol=1e-3)
print("CHECK fused_dots OK")

# -- 4. CG runs unchanged on the engine -------------------------------------
r, c, v, n = matpde(16, beta_c=0.0)
A = np.zeros((n, n)); A[r, c] += v
engs = HeterogeneousEngine(r, c, v, n, mesh=mesh, pool=pool, C=8, sigma=16,
                           w_align=4, dtype=np.float32)
op = make_operator(engs)
b = rng.standard_normal((n, 2)).astype(np.float32)
res = cg(op, op.to_op_space(b), tol=1e-6, maxiter=600)
assert bool(np.asarray(res.converged).all())
xsol = np.asarray(op.from_op_space(res.x))
assert np.abs(A @ xsol - b).max() < 1e-3
print("CHECK cg_solver OK")

# -- 5. split follows the pool ----------------------------------------------
sizes = eng.plan.sizes
assert abs(sizes[1] / sizes[0] - 3.0) < 0.3, sizes   # 150/50 bandwidth ratio
print("CHECK weighted_split OK")
""")
