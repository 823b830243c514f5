"""ELL→BSR streaming backend: registry capabilities, allclose-vs-ref
parity on insert/delete streams, slot-budget overflow fallback, ladder-
bounded compile accounting, and the sharded bit-equality contract.

All Pallas work runs in interpret mode on CPU (what ``interpret=None``
resolves to off-TPU); the 8-device cross-transport check forces a virtual
mesh in a subprocess like tests/test_stream_sharded.py.
"""

import logging
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.snapshot import ladder_size
from repro.core.stream import StreamEngine
from repro.data.synth import StreamSpec, gaussian_mixture_stream
from repro.graph.dynamic import UNLABELED, BatchUpdate, DynamicGraph
from repro.kernels import ops

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# bsr sums edges in tile order, so residuals near the δ threshold can
# lag ref by O(δ); the registry contract is allclose, not bit-equality.
BSR_ATOL = 2e-3


def _empty_batch(dim):
    return BatchUpdate(ins_emb=np.zeros((0, dim), np.float32),
                       ins_labels=np.zeros(0, np.int8),
                       del_ids=np.zeros(0, np.int64))


# ------------------------------------------------------------------ #
# registry
# ------------------------------------------------------------------ #
def test_registry_declares_capabilities():
    """Every backend is a registry entry with declared capabilities —
    the dispatch layer has no hard-coded backend names left."""
    assert ops.backend_names() == ("ref", "ell_pallas", "bsr", "landmark")
    for name in ops.backend_names():
        spec = ops.backend_spec(name)
        assert spec.sharded  # all four have a core.distributed body
        assert spec.transports == ("allgather", "halo")
        assert callable(spec.auto_eligible) and callable(spec.run)
    with pytest.raises(ValueError, match="unknown backend"):
        ops.backend_spec("csr")
    with pytest.raises(ValueError, match="unknown backend"):
        ops.select_backend("csr")


def test_registry_auto_eligibility_rules(monkeypatch):
    """auto never picks bsr without a measured fill factor, and the fill
    threshold gates it even on (simulated) TPU."""
    monkeypatch.delenv("REPRO_BACKEND", raising=False)  # true auto
    info_nofill = ops.ProblemInfo(num_rows=4096)
    info_dense = ops.ProblemInfo(num_rows=4096, block_fill=0.9)
    info_sparse = ops.ProblemInfo(num_rows=4096, block_fill=0.01)
    bsr = ops.backend_spec("bsr")
    assert not bsr.auto_eligible(info_nofill, "tpu")
    assert bsr.auto_eligible(info_dense, "tpu")
    assert not bsr.auto_eligible(info_sparse, "tpu")
    assert not bsr.auto_eligible(info_dense, "cpu")
    # priority order: bsr outranks ell_pallas outranks ref
    prios = [ops.backend_spec(n).auto_priority
             for n in ("bsr", "ell_pallas", "ref")]
    assert prios == sorted(prios, reverse=True)
    # off-TPU auto stays on ref regardless of fill
    assert ops.select_backend("auto", num_rows=4096, block_fill=0.9) == "ref"


def test_bsr_block_size_is_per_hardware_registry_property():
    """Block edge comes from the bsr BackendSpec per hardware — MXU-sized
    on TPU, interpret-friendly elsewhere — and the auto fill threshold
    re-derives from it (break-even density ~ 2/edge)."""
    assert ops.bsr_block_size("tpu") == 128
    assert ops.bsr_block_size("cpu") == 8
    assert ops.bsr_block_size("gpu") == 8
    # the process default resolves through jax.default_backend()
    import jax
    assert ops.bsr_block_size() == ops.bsr_block_size(jax.default_backend())
    assert ops.bsr_auto_fill_min("cpu") == 2.0 / 8
    assert ops.bsr_auto_fill_min("tpu") == 2.0 / 128
    # eligibility tracks the per-hardware threshold: a fill that is too
    # sparse for 8-wide blocks clears the 128-wide TPU break-even
    bsr = ops.backend_spec("bsr")
    info = ops.ProblemInfo(num_rows=4096, block_fill=0.05)
    assert bsr.auto_eligible(info, "tpu")
    assert 0.05 < ops.bsr_auto_fill_min("cpu")


# ------------------------------------------------------------------ #
# stream parity
# ------------------------------------------------------------------ #
def test_bsr_stream_matches_ref_insert_delete():
    """Mixed insert/delete stream through backend='bsr' (component
    reorder + device-side tile fill, interpret mode) stays allclose to
    the ref engine; every solved batch reports backend='bsr'."""
    spec = StreamSpec(total_vertices=300, batch_size=60, seed=9,
                      class_sep=6.0, noise=0.9, frac_deleted=0.15,
                      frac_unlabeled=0.84)
    g_b = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    g_r = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    eng_b = StreamEngine(g_b, delta=1e-4, backend="bsr")
    eng_r = StreamEngine(g_r, delta=1e-4, backend="ref")
    stats = []
    for batch, _ in gaussian_mixture_stream(spec):
        stats.append(eng_b.step(batch))
        eng_r.step(batch)
    assert {s.backend for s in stats} == {"bsr"}
    assert eng_b.bsr_batches == len(stats)
    assert eng_b.backend_overflows == 0
    summary = eng_b.transport_summary()
    assert set(summary["rung_backends"].values()) == {"bsr"}
    assert all(b >= 1 for b in summary["slot_budgets"].values())
    np.testing.assert_allclose(g_b.f, g_r.f, atol=BSR_ATOL)


def test_bsr_empty_frontier_noop_commits():
    """A no-op Δ_t on a bsr engine stages nothing — no reorder, no tile
    fill — but still commits, and the next real batch resumes."""
    rng = np.random.default_rng(2)
    g = DynamicGraph(emb_dim=4, k=3)
    eng = StreamEngine(g, delta=1e-4, backend="bsr")
    emb = rng.normal(0, 1, (24, 4)).astype(np.float32)
    emb[0, 0], emb[1, 0] = 3.0, -3.0
    labels = np.full(24, UNLABELED, np.int8)
    labels[0], labels[1] = 1, 0
    eng.step(BatchUpdate(ins_emb=emb, ins_labels=labels,
                         del_ids=np.zeros(0, np.int64)))
    st = eng.step(_empty_batch(4))
    assert st.converged and st.backend == "none" and st.transport == "none"
    st = eng.step(BatchUpdate(
        ins_emb=rng.normal([3, 0, 0, 0], 0.1, (8, 4)).astype(np.float32),
        ins_labels=np.full(8, UNLABELED, np.int8),
        del_ids=np.zeros(0, np.int64)))
    assert st.converged and st.backend == "bsr"
    assert eng.commits == 3


def test_bsr_slot_budget_overflow_falls_back_with_warning(caplog):
    """A Δ_t whose tile-slot requirement exceeds the rung's compiled
    budget runs on the registry's resolution with bsr out of the scan
    (``ref`` here) instead (warned once per rung), and the labels still
    track ref — mirroring the halo-overflow contract."""
    spec = StreamSpec(total_vertices=240, batch_size=60, seed=5,
                      class_sep=6.0, noise=0.9)
    g = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    g_r = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    eng = StreamEngine(g, delta=1e-4, backend="bsr", block_rows=64)
    ref = StreamEngine(g_r, delta=1e-4, backend="ref")
    stats = []
    with caplog.at_level(logging.WARNING, logger="repro.core.stream"):
        for i, (batch, _) in enumerate(gaussian_mixture_stream(spec)):
            stats.append(eng.step(batch))
            ref.step(batch)
            if i == 0:
                # sabotage every known rung budget: later batches in the
                # rung must overflow and fall back
                for key in list(eng._slot_budgets):
                    eng._slot_budgets[key] = 1
    fallbacks = [s for s in stats if s.backend not in ("bsr", "none")]
    assert fallbacks, "sabotaged slot budget never overflowed"
    assert {s.backend for s in fallbacks} == {"ref"}
    assert eng.backend_overflows == len(fallbacks)
    warned = [r for r in caplog.records if "tile slots" in r.getMessage()]
    assert warned and len(warned) <= len(eng.bucket_keys)
    np.testing.assert_allclose(g.f, g_r.f, atol=BSR_ATOL)


def test_env_hint_pinned_at_construction(monkeypatch):
    """A mid-stream REPRO_BACKEND flip must not change (or crash) an
    already-built engine: the hint is read once, at construction, where
    the row padding and candidate set it implies are decided.  A fresh
    engine built under the flipped hint picks it up."""
    spec = StreamSpec(total_vertices=160, batch_size=40, seed=3,
                      class_sep=6.0, noise=0.9)
    batches = [b for b, _ in gaussian_mixture_stream(spec)]
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    g = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    eng = StreamEngine(g, delta=1e-3)
    eng.step(batches[0])
    monkeypatch.setenv("REPRO_BACKEND", "bsr")
    for b in batches[1:]:  # crosses a rung boundary under the flipped env
        st = eng.step(b)
        if st.backend != "none":
            assert st.backend == "ref", st.backend  # pinned, not re-read
    g2 = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    eng2 = StreamEngine(g2, delta=1e-3)  # built under the hint
    assert eng2.step(batches[0]).backend == "bsr"


@given(st.integers(0, 1_000))
@settings(max_examples=3, deadline=None)
def test_bsr_compile_cache_stays_ladder_bounded(seed):
    """Property arm: for ANY random stream, backend='bsr' keeps the
    registry's compile accounting within the bucket ladder (+1 per
    recorded slot-budget overflow — the overflow twin)."""
    rng = np.random.default_rng(seed)
    spec = StreamSpec(total_vertices=int(rng.integers(150, 400)),
                      batch_size=int(rng.integers(40, 90)),
                      seed=int(rng.integers(0, 100)),
                      class_sep=6.0, noise=0.9,
                      frac_deleted=float(rng.uniform(0, 0.2)),
                      frac_unlabeled=0.8)
    g = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    eng = StreamEngine(g, delta=1e-3, backend="bsr")
    cache0 = ops.compile_cache_size()
    for batch, _ in gaussian_mixture_stream(spec):
        eng.step(batch)
    grown = ops.compile_cache_size() - cache0
    max_k = max(k for _, k in eng.bucket_keys)
    bound = ladder_size(spec.total_vertices + 256, max_k)
    assert grown <= bound + eng.backend_overflows, (
        grown, bound, eng.backend_overflows, eng.bucket_keys)
    assert eng.recompile_count <= len(eng.bucket_keys) + eng.backend_overflows


# ------------------------------------------------------------------ #
# sharded: the acceptance contract
# ------------------------------------------------------------------ #
SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys
    sys.path.insert(0, {src!r})
    import numpy as np
    from repro.core.stream import StreamEngine
    from repro.data.synth import StreamSpec, gaussian_mixture_stream
    from repro.graph.dynamic import DynamicGraph
    from repro.launch.mesh import make_stream_mesh

    spec = StreamSpec(total_vertices=400, batch_size=50, seed=11,
                      class_sep=6.0, noise=0.9, frac_deleted=0.15,
                      frac_unlabeled=0.84)
    batches = [b for b, _ in gaussian_mixture_stream(spec)]
    mesh = make_stream_mesh()
    assert mesh.devices.size == 8

    g_ref = DynamicGraph(emb_dim=spec.emb_dim, k=5)
    ref = StreamEngine(g_ref, delta=1e-4)
    engines = {{}}
    for tr in ("allgather", "halo"):
        g = DynamicGraph(emb_dim=spec.emb_dim, k=5)
        engines[tr] = (g, StreamEngine(g, delta=1e-4, backend="bsr",
                                       mesh=mesh, transport=tr))
    for b in batches:
        ref.step(b)
        for g, e in engines.values():
            e.step(b)
    ga, ea = engines["allgather"]
    gh, eh = engines["halo"]
    # the acceptance headline: bsr rides both transports, labels
    # bit-identical across them (identical halo row layout => identical
    # tile layout => identical MXU sums) and allclose to ref
    assert np.array_equal(ga.f, gh.f), np.abs(ga.f - gh.f).max()
    assert np.abs(ga.f - g_ref.f).max() <= {atol}, (
        np.abs(ga.f - g_ref.f).max())
    # every batch solved on bsr, plans reused per rung, no overflows
    for e in (ea, eh):
        assert e.bsr_batches == len(batches), e.transport_summary()
        assert e.backend_overflows == 0
        assert e.plan_builds <= len(e.bucket_keys) + e.transport_overflows
    assert eh.halo_batches + eh.transport_overflows == len(batches)
    # sharded buckets tile evenly into both the mesh and the BSR grid
    assert all(u % (8 * 8) == 0 for u, _ in ea.bucket_keys), ea.bucket_keys
    print("OK sharded-bsr", len(ea.bucket_keys), "rungs",
          ea.plan_builds, "plans", eh.halo_batches, "halo batches")
""")


@pytest.mark.slow
def test_sharded_bsr_bit_identical_across_transports_8dev():
    """backend='bsr' through StreamEngine(mesh=..., transport=
    'halo'|'allgather') on a forced 8-device CPU mesh: labels bit-equal
    across transports, allclose to ref, plans reused per rung."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_STREAM_TRANSPORT", None)
    env.pop("REPRO_BACKEND", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=SRC, atol=BSR_ATOL)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK sharded-bsr" in out.stdout
