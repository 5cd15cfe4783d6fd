"""Mesh-sharded serving engine: DP/TP parity, per-shard accounting and
the MeshPlan surface.

Runs in-process under the conftest multi-device harness (8 virtual CPU
devices by default via REPRO_FORCE_DEVICES).  Parity contract
(distributed/serve_mesh.py):

  * pure DP (``dx1``): per-row arithmetic is untouched, so greedy
    streams are BIT-IDENTICAL to the single-device engine;
  * TP (``model > 1``): splitting the down-projection contraction
    reorders the fp32 reduction, so streams are argmax-equivalent --
    same lengths, same content unless an argmax tie flips on a ~1 ulp
    logit perturbation.  The smoke configs have no such ties, so we
    assert exact equality there too, but the *guaranteed* contract is
    per-token plausibility, which test_tp_logits_close pins directly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import archs
from repro.distributed import serve_mesh
from repro.models import lm
from repro.serving.engine import COMPLETED, ServingEngine
from repro.serving.scheduler import ShardStats

pytestmark = pytest.mark.slow


def _need_devices(n: int):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (REPRO_FORCE_DEVICES)")


@pytest.fixture(scope="module")
def setup():
    cfg = archs.smoke("mingru-lm")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _submit_all(eng, cfg, n, seed=7, max_new=8, temperature=0.0,
                **kw):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        p = rng.randint(1, cfg.vocab_size,
                        size=rng.randint(3, 12)).tolist()
        eng.submit(p, max_new=max_new, temperature=temperature, **kw)


def _run(cfg, params, mesh, n_req=9, **ekw):
    eng = ServingEngine(cfg, params, max_batch=4, max_len=96,
                        decode_block=4, mesh=mesh, **ekw)
    _submit_all(eng, cfg, n_req)
    return eng.run_to_completion(), eng


# ---------------------------------------------------------------------------
# MeshPlan surface
# ---------------------------------------------------------------------------

def test_mesh_plan_parse():
    assert serve_mesh.MeshPlan.parse(None) is None
    p = serve_mesh.MeshPlan.parse("4x2")
    assert (p.data, p.model, p.size, str(p)) == (4, 2, 8, "4x2")
    assert serve_mesh.MeshPlan.parse(p) is p
    for bad in ("4", "x2", "2x2x2", "ax1", "2*2", ""):
        with pytest.raises(ValueError):
            serve_mesh.MeshPlan.parse(bad)
    with pytest.raises(ValueError):
        serve_mesh.MeshPlan(0, 1)


def test_mesh_plan_build_too_many_devices_actionable():
    plan = serve_mesh.MeshPlan(1024, 1)
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        plan.build()


def test_engine_validates_mesh(setup):
    cfg, params = setup
    _need_devices(2)
    with pytest.raises(ValueError, match="divide over the data"):
        ServingEngine(cfg, params, max_batch=3, mesh="2x1")
    # d_hidden = 128 on the smoke config: model=3 does not divide it
    with pytest.raises(ValueError, match="does not divide"):
        ServingEngine(cfg, params, max_batch=3, mesh="1x3")


# ---------------------------------------------------------------------------
# DP parity: bit-exact greedy streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", ["2x1", "4x1"])
def test_dp_greedy_bit_exact(setup, mesh):
    cfg, params = setup
    _need_devices(serve_mesh.MeshPlan.parse(mesh).size)
    ref, _ = _run(cfg, params, None)
    out, eng = _run(cfg, params, mesh)
    assert out == ref
    assert eng.stats.completed == len(ref)
    assert eng.stats.shard_identities_ok()


def test_dp_speculative_bit_exact(setup):
    """Drafting under a DP mesh never changes content -- streams match
    the plain single-device engine bit for bit, and drafts are actually
    accepted (the spec path really ran)."""
    cfg, params = setup
    _need_devices(2)
    ref, _ = _run(cfg, params, None)
    out, eng = _run(cfg, params, "2x1", speculative="ngram")
    assert out == ref
    assert eng.stats.draft_accepted > 0
    assert eng.stats.shard_identities_ok()


def test_dp_sampled_determinism_and_single_row_parity(setup):
    """Sampling keys are per-ROW, so multi-request sampled streams are
    placement-dependent (the shard-aware stager may balance requests
    onto different rows than the meshless ``(eta, row)`` order) -- but a
    run is deterministic given (mesh, seed), and a single request lands
    on row 0 under every shape, where parity is exact."""
    cfg, params = setup
    _need_devices(4)

    def sampled(mesh, n):
        eng = ServingEngine(cfg, params, max_batch=4, max_len=96,
                            decode_block=4, mesh=mesh, seed=11)
        _submit_all(eng, cfg, n, max_new=10, temperature=0.8)
        return eng.run_to_completion()

    assert sampled("2x1", 6) == sampled("2x1", 6)
    assert sampled(None, 1) == sampled("2x1", 1) == sampled("4x1", 1)


# ---------------------------------------------------------------------------
# TP parity: argmax-equivalent streams, close logits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", ["1x2", "2x2"])
def test_tp_greedy_streams(setup, mesh):
    cfg, params = setup
    _need_devices(serve_mesh.MeshPlan.parse(mesh).size)
    ref, _ = _run(cfg, params, None, n_req=6)
    out, eng = _run(cfg, params, mesh, n_req=6)
    assert set(out) == set(ref)
    for rid in ref:
        assert len(out[rid]) == len(ref[rid]), rid
        assert out[rid] == ref[rid], \
            f"rid {rid}: TP stream diverged beyond an argmax tie"
    assert eng.stats.shard_identities_ok()


def test_tp_logits_close(setup):
    """The guaranteed TP contract, pinned below the argmax: one sharded
    decode step reproduces single-device logits to fp32 reduction-order
    tolerance."""
    cfg, params = setup
    _need_devices(2)
    from jax.sharding import PartitionSpec as P
    from repro.distributed import context as mesh_ctx

    plan = serve_mesh.MeshPlan(1, 2)
    mesh = plan.build()
    cache = lm.init_cache(cfg, 2, 32)
    toks = jnp.asarray([3, 5], jnp.int32)
    ref, _ = jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c))(
        params, toks, cache)

    pspecs = serve_mesh.serve_params_pspecs(params, cfg, plan, mesh)
    cspecs = serve_mesh._cache_pspecs(cache, True)

    def body(p, c):
        with mesh_ctx.serving_tp("model"):
            return lm.decode_step(p, cfg, toks, c)

    fn = mesh_ctx.shard_map(body, mesh=mesh, in_specs=(pspecs, cspecs),
                            out_specs=(P(), cspecs), check_vma=False)
    out, _ = jax.jit(fn)(params, cache)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Per-shard accounting
# ---------------------------------------------------------------------------

def test_shard_stats_identity_and_aggregation(setup):
    cfg, params = setup
    _need_devices(4)
    out, eng = _run(cfg, params, "4x1")
    st = eng.stats
    assert len(st.shards) == 4
    # per-shard identity AND the cross-shard sums reproduce the globals
    assert st.shard_identities_ok()
    assert sum(s.slot_steps for s in st.shards) == st.slot_steps
    assert sum(s.decode_tokens for s in st.shards) == st.decode_tokens
    assert sum(s.prefill_rounds for s in st.shards) == st.prefill_rounds
    assert sum(s.wasted_slot_steps for s in st.shards) \
        == st.wasted_slot_steps
    assert sum(s.non_spec_tokens for s in st.shards) == st.non_spec_tokens
    snap = st.snapshot()
    assert snap["n_shards"] == 4
    assert snap["shard_identities_ok"]
    assert len(snap["shards"]) == 4


@pytest.mark.parametrize("mesh,one_at_a_time", [("1x2", False),
                                                 ("2x1", True)])
def test_packed_counters_match_single_device(setup, mesh, one_at_a_time):
    """``packed_rounds`` / ``packed_tokens`` ride the mesh superstep's
    per-shard counters.  Each data shard takes the packed branch on its
    own rows, so the sums equal the single-device engine's whenever one
    data shard packs at a time: always with one data shard (TP alone),
    and under DP with one request in flight."""
    cfg, params = setup
    _need_devices(serve_mesh.MeshPlan.parse(mesh).size)

    def run(m):
        eng = ServingEngine(cfg, params, max_batch=4, max_len=96,
                            decode_block=4, prompt_chunk=4, mesh=m)
        rng = np.random.RandomState(7)
        for _ in range(5):
            eng.submit(rng.randint(1, cfg.vocab_size,
                                   size=rng.randint(3, 12)).tolist(),
                       max_new=6)
            if one_at_a_time:
                eng.run_to_completion()
        eng.run_to_completion()
        return eng.stats

    ref, got = run(None), run(mesh)
    assert got.packed_rounds == ref.packed_rounds > 0
    assert got.packed_tokens == ref.packed_tokens > 0


def test_wasted_slot_steps_land_on_the_idle_shard(setup):
    """One long request pins shard 0 while shard 1 sits empty: the idle
    shard accrues the wasted slot-steps, the busy one the work."""
    cfg, params = setup
    _need_devices(2)
    eng = ServingEngine(cfg, params, max_batch=4, max_len=96,
                        decode_block=4, mesh="2x1")
    eng.submit([5, 6, 7], max_new=12)
    eng.run_to_completion()
    s0, s1 = eng.stats.shards
    assert s0.decode_tokens == 12 and s1.decode_tokens == 0
    # shard 1 never armed anything: every one of its slot-steps is waste
    assert s1.wasted_slot_steps == s1.slot_steps
    assert s0.wasted_slot_steps < s0.slot_steps
    assert eng.stats.shard_identities_ok()


def test_stager_balances_shards(setup):
    """Two concurrent requests must land on DIFFERENT shards (the
    least-loaded placement), not both on shard 0."""
    cfg, params = setup
    _need_devices(2)
    eng = ServingEngine(cfg, params, max_batch=4, max_len=96,
                        decode_block=2, mesh="2x1")
    eng.submit([5, 6, 7], max_new=6)
    eng.submit([8, 9], max_new=6)
    eng.run_to_completion()
    s0, s1 = eng.stats.shards
    assert s0.decode_tokens == 6 and s1.decode_tokens == 6


def test_cancel_and_deadline_on_nonzero_shard(setup):
    """Lifecycle machinery is shard-agnostic: kill an in-flight request
    running on shard 1 (cancel) and time one out there; partial output
    survives and the identities still hold."""
    cfg, params = setup
    _need_devices(2)
    eng = ServingEngine(cfg, params, max_batch=4, max_len=96,
                        decode_block=2, mesh="2x1")
    r0 = eng.submit([5, 6, 7], max_new=40)
    r1 = eng.submit([8, 9, 10], max_new=40)            # -> shard 1
    eng.step()
    assert eng.requests[r1].slot >= eng._rows_per_shard
    while not eng.requests[r1].out:
        eng.step()
    assert eng.cancel(r1)
    out = eng.run_to_completion()
    assert eng.finished[r1].status == "CANCELLED"
    assert 0 < len(out[r1]) < 40                       # partial preserved
    assert eng.finished[r0].status == COMPLETED
    assert eng.stats.shard_identities_ok()

    eng2 = ServingEngine(cfg, params, max_batch=4, max_len=96,
                         decode_block=2, mesh="2x1")
    # both carry deadlines so EDF keeps submission order (a lone
    # deadline would jump the queue and land on shard 0)
    d0 = eng2.submit([5, 6, 7], max_new=40, deadline=500)
    d1 = eng2.submit([8, 9, 10], max_new=40, deadline=512)
    eng2.step()
    assert eng2.requests[d1].slot >= eng2._rows_per_shard   # on shard 1
    # the capacity estimate admits the feasible deadline; simulate it
    # having been wrong by tightening post-admission (test_faults idiom)
    eng2.requests[d1].deadline = eng2.stats.decode_steps
    eng2.run_to_completion()
    assert eng2.requests[d1].slot is None
    assert eng2.finished[d1].status == "TIMED_OUT"
    assert eng2.finished[d0].status == COMPLETED
    assert eng2.stats.shard_identities_ok()


def test_shard_stats_identity_definition():
    """The identity itself, on hand-built numbers (doc for the field
    semantics: every slot-step is prefill, emitted decode, first-token
    overlap, waste or a health-guard kill)."""
    s = ShardStats(slot_steps=10, prefill_rounds=4, decode_tokens=5,
                   first_tokens=2, wasted_slot_steps=3,
                   nonfinite_decode_rounds=0, non_spec_tokens=5)
    assert s.identity_ok()
    s.wasted_slot_steps = 2
    assert not s.identity_ok()


def test_meshless_engine_has_single_shard(setup):
    """dp=1 always: the per-shard machinery runs (one shard covering the
    whole pool) so the identity is continuously checked even meshless."""
    cfg, params = setup
    out, eng = _run(cfg, params, None, n_req=5)
    assert len(eng.stats.shards) == 1
    st = eng.stats
    assert st.shards[0].slot_steps == st.slot_steps
    assert st.shard_identities_ok()
