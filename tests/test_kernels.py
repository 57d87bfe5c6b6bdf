"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.kernels.corr_diff.ops import corr_moments
from repro.kernels.corr_diff.ref import corr_diff_ref
from repro.kernels.hash_threshold.ops import hash_threshold
from repro.kernels.hash_threshold.ref import hash_threshold_ref
from repro.kernels.segment_aggsum.ops import segment_sum
from repro.kernels.segment_aggsum.ref import segment_sum_ref


@pytest.mark.parametrize("n", [1, 127, 128, 129, 8192, 10000])
@pytest.mark.parametrize("ncols", [1, 2, 3])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
def test_hash_threshold_sweep(n, ncols, dtype):
    rng = np.random.default_rng(n * 7 + ncols)
    cols = [jnp.asarray(rng.integers(0, 2**31 - 1, n).astype(dtype))
            for _ in range(ncols)]
    got = np.asarray(hash_threshold(cols, 0.31, seed=4))
    want = np.asarray(hash_threshold_ref(cols, 0.31, seed=4))
    assert np.array_equal(got, want)


def test_u01_matches_xla_uint32_convert():
    """The split conversion every η kernel shares gives the bits of XLA's
    uint32 → float32 convert: random words plus each power-of-two edge and
    the round-to-nearest-even ties above 2^24."""
    import jax

    from repro.core.hashing import u01

    rng = np.random.default_rng(11)
    words = [rng.integers(0, 2**32, 1 << 20, dtype=np.uint64)]
    for k in range(33):
        words.append(np.array([2**k - 1, 2**k, 2**k + 1], np.uint64))
    for e in range(24, 32):
        half = 1 << (e - 24)
        base = rng.integers(1 << e, min(2 * (1 << e), 2**32), 256,
                            dtype=np.uint64) & ~np.uint64(2 * half - 1)
        words += [base + half - 1, base + half, base + half + 1]
    x = np.concatenate(words) % 2**32
    x = x.astype(np.uint32)
    want = jax.jit(lambda h: h.astype(jnp.float32)
                   * jnp.float32(1.0 / 4294967296.0))(x)
    got = jax.jit(u01)(x)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


@given(m=st.floats(0.0, 1.0), seed=st.integers(0, 100))
@settings(max_examples=15, deadline=None)
def test_hash_threshold_ratio_property(m, seed):
    keys = jnp.arange(4096, dtype=jnp.int32)
    frac = float(np.mean(np.asarray(hash_threshold([keys], m, seed))))
    assert abs(frac - m) < 0.05


@pytest.mark.parametrize("shape", [(100, 1, 10), (1000, 4, 50), (4096, 8, 300),
                                   (257, 3, 129), (1, 1, 1)])
def test_segment_sum_sweep(shape):
    R, C, G = shape
    rng = np.random.default_rng(R)
    gid = jnp.asarray(rng.integers(0, G, R).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(R, C)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(segment_sum(gid, vals, G)),
        np.asarray(segment_sum_ref(gid, vals, G)),
        rtol=1e-5, atol=1e-4,
    )


def test_segment_sum_drops_out_of_range():
    gid = jnp.asarray(np.array([0, 1, 99, -1], np.int32))
    vals = jnp.ones((4, 1), jnp.float32)
    out = np.asarray(segment_sum(gid, vals, 2))
    np.testing.assert_allclose(out[:, 0], [1.0, 1.0])


@pytest.mark.parametrize("n", [1, 300, 8192, 20000])
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_corr_moments_sweep(n, density):
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.normal(size=n).astype(np.float32))
    b = jnp.asarray(rng.normal(size=n).astype(np.float32))
    mask = jnp.asarray(rng.random(n) < density)
    got = [float(x) for x in corr_moments(a, b, mask)]
    want = [float(x) for x in corr_diff_ref(a, b, mask)]
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-3)


def test_pallas_dispatch_switch():
    import repro.kernels as K
    from repro.core import hashing

    cols = [jnp.arange(5000, dtype=jnp.int32)]
    base = np.asarray(hashing.hash_threshold_mask(cols, 0.2, 9))
    K.enable()
    try:
        pal = np.asarray(hashing.hash_threshold_mask(cols, 0.2, 9))
    finally:
        K.disable()
    assert np.array_equal(base, pal)


# ---------------------------------------------------------------------------
# fused clean_sample (η filter + group sum/count in one pass)
# ---------------------------------------------------------------------------

from repro.kernels.fused_clean.ops import fused_clean_groupby
from repro.kernels.fused_clean.ref import fused_clean_ref


@pytest.mark.parametrize("shape", [(1, 64), (300, 100), (5000, 700), (257, 129)])
@pytest.mark.parametrize("pin_density", [0.0, 0.05])
def test_fused_clean_matches_ref(shape, pin_density):
    R, G = shape
    rng = np.random.default_rng(R + G)
    gid = jnp.asarray(rng.integers(0, G, R).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))
    valid = jnp.asarray(rng.random(R) < 0.9)
    pin = jnp.asarray(rng.random(R) < pin_density) if pin_density else None
    # use_pallas=True: exercise the kernel body (interpret mode on CPU)
    c1, s1 = fused_clean_groupby(gid, vals, valid, 0.3, 7, G, pin_mask=pin,
                                 use_pallas=True)
    c2, s2 = fused_clean_ref(gid, vals, valid, 0.3, 7, G, pin_mask=pin)
    assert np.array_equal(np.asarray(c1), np.asarray(c2))  # counts: exact
    # sums too: a group's rows accumulate one at a time in row order, the
    # reference segment sum's float order (G < R puts many rows of a
    # group in one row tile)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_fused_clean_drops_out_of_range_and_invalid():
    gid = jnp.asarray(np.array([0, 1, 99, -1, 1], np.int32))
    vals = jnp.ones((5, 1), jnp.float32)
    valid = jnp.asarray(np.array([True, True, True, True, False]))
    c, s = fused_clean_groupby(gid, vals, valid, 1.0, 0, 2, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(c), [1.0, 1.0])
    np.testing.assert_array_equal(np.asarray(s)[:, 0], [1.0, 1.0])


def _clean_scenario(integer_bytes: bool, m=0.2, seed=5, n_videos=300, n_logs=6000):
    """visitView scenario; integer-valued bytes make float sums order-exact."""
    from repro.core import ViewDef
    from repro.data.synthetic import grow_log, make_log_video
    from repro.relational.plan import FKJoin, GroupByNode, Scan
    from repro.relational.relation import from_columns, to_host
    from repro.views import ViewManager

    rng = np.random.default_rng(1)
    log, video = make_log_video(rng, n_videos, n_logs)
    delta = grow_log(rng, n_videos, n_logs, 1500)
    if integer_bytes:
        def intify(rel):
            h = to_host(rel)
            h["bytes"] = np.round(h["bytes"]).astype(np.float32)
            return from_columns(h, pk=rel.schema.pk)

        log, delta = intify(log), intify(delta)
    plan = GroupByNode(
        child=FKJoin(fact=Scan("Log", pk=("sessionId",)),
                     dim=Scan("Video", pk=("videoId",)), fact_key="videoId"),
        keys=("videoId",),
        aggs=(("visitCount", "count", None), ("totalBytes", "sum", "bytes")),
        num_groups=512,
    )
    vm = ViewManager()
    vm.register_base("Log", log)
    vm.register_base("Video", video)
    vm.register_view(ViewDef("v", plan), delta_bases=("Log",), m=m, seed=seed,
                     delta_group_capacity=512)
    vm.ingest("Log", inserts=delta)
    return vm


def _sorted_host(rel):
    from repro.relational.relation import to_host

    h = to_host(rel)
    order = np.argsort(h["videoId"], kind="stable")
    return {k: v[order] for k, v in h.items()}


def test_fused_clean_sample_bitexact_vs_plan_executor():
    """Acceptance: fused dispatch == unfused plan path bit-for-bit on the
    sum/count group aggregates (integer-valued data ⇒ order-independent)."""
    vm_f = _clean_scenario(integer_bytes=True)
    vm_u = _clean_scenario(integer_bytes=True)
    vm_f.svc_refresh("v", fused=True)
    vm_u.svc_refresh("v", fused=False)
    a = _sorted_host(vm_f.views["v"].clean_sample)
    b = _sorted_host(vm_u.views["v"].clean_sample)
    assert set(a) == set(b)
    for col in ("videoId", "visitCount", "totalBytes"):
        assert np.array_equal(a[col], b[col]), col


def test_fused_clean_sample_parity_continuous():
    """Continuous values: identical sample membership, sums to fp tolerance."""
    vm_f = _clean_scenario(integer_bytes=False)
    vm_u = _clean_scenario(integer_bytes=False)
    vm_f.svc_refresh("v", fused=True)
    vm_u.svc_refresh("v", fused=False)
    a = _sorted_host(vm_f.views["v"].clean_sample)
    b = _sorted_host(vm_u.views["v"].clean_sample)
    assert np.array_equal(a["videoId"], b["videoId"])
    assert np.array_equal(a["visitCount"], b["visitCount"])
    np.testing.assert_allclose(a["totalBytes"], b["totalBytes"], rtol=1e-5)


def test_fused_clean_sample_outlier_pin_stratum():
    """The pin set (Def. 5) enters the sample with weight 1 on both paths."""
    vm_f = _clean_scenario(integer_bytes=True)
    vm_u = _clean_scenario(integer_bytes=True)
    for vm in (vm_f, vm_u):
        vm.register_outlier_index("v", "Log", "bytes", k=40)
    vm_f.svc_refresh("v", fused=True)
    vm_u.svc_refresh("v", fused=False)
    a = _sorted_host(vm_f.views["v"].clean_sample)
    b = _sorted_host(vm_u.views["v"].clean_sample)
    assert np.array_equal(a["videoId"], b["videoId"])
    assert np.array_equal(a["visitCount"], b["visitCount"])
    assert np.array_equal(a["totalBytes"], b["totalBytes"])
    # the weight-1 stratum is flagged identically and non-empty
    assert np.array_equal(a["__outlier"], b["__outlier"])
    assert a["__outlier"].sum() > 0


def test_fused_dispatch_falls_back_on_negative_keys():
    """Negative group keys never land in the dense accumulator; the
    dispatcher must fall back so fused == unfused on such views."""
    from repro.core import ViewDef
    from repro.relational.plan import GroupByNode, Scan
    from repro.relational.relation import from_columns
    from repro.views import ViewManager

    def build():
        base = from_columns(
            {"k": np.array([-3, 0, 1, 2], np.int32),
             "v": np.array([1.0, 2.0, 3.0, 4.0], np.float32),
             "rid": np.arange(4, dtype=np.int32)},
            pk=["rid"],
        )
        plan = GroupByNode(child=Scan("T", pk=("rid",)), keys=("k",),
                           aggs=(("total", "sum", "v"), ("n", "count", None)),
                           num_groups=64)
        vm = ViewManager()
        vm.register_base("T", base)
        vm.register_view(ViewDef("neg", plan), delta_bases=("T",), m=1.0,
                         delta_group_capacity=64)
        delta = from_columns(
            {"k": np.array([-3, 5], np.int32),
             "v": np.array([10.0, 20.0], np.float32),
             "rid": np.array([100, 101], np.int32)},
            pk=["rid"],
        )
        vm.ingest("T", inserts=delta)
        return vm

    vm_f, vm_u = build(), build()
    vm_f.svc_refresh("neg", fused=True)
    vm_u.svc_refresh("neg", fused=False)
    from repro.relational.relation import to_host

    def rows(vm):
        h = to_host(vm.views["neg"].clean_sample)
        order = np.argsort(h["k"], kind="stable")
        return {c: v[order] for c, v in h.items()}

    a, b = rows(vm_f), rows(vm_u)
    assert np.array_equal(a["k"], b["k"])  # group -3 must survive both paths
    assert -3 in a["k"].tolist()
    assert np.array_equal(a["total"], b["total"])
    assert np.array_equal(a["n"], b["n"])


def test_fuse_delta_groupbys_two_groupbys_one_leaf_no_collision():
    """Two fusable group-bys over the SAME delta leaf must splice under
    DISTINCT env names (the seed named both '__fused__'+leaf: the second
    silently overwrote the first and both branches read one result)."""
    import jax.numpy as jnp

    from repro.core.maintenance import fuse_delta_groupbys
    from repro.relational.execute import execute
    from repro.relational.plan import GroupByNode, HashNode, Scan, UnionNode
    from repro.relational.relation import from_columns, to_host

    fact = from_columns(
        {"rid": np.arange(8, dtype=np.int32),
         "g": np.array([0, 0, 1, 1, 2, 2, 3, 3], np.int32),
         "v": np.arange(8, dtype=np.float32),
         "w": 10.0 * np.arange(8, dtype=np.float32)},
        pk=["rid"],
    )
    eta = HashNode(child=Scan("T__ins", pk=("rid",)), cols=("g",), m=1.0, seed=0)
    g_v = GroupByNode(child=eta, keys=("g",), aggs=(("a", "sum", "v"),), num_groups=16)
    g_w = GroupByNode(child=eta, keys=("g",), aggs=(("a", "sum", "w"),), num_groups=16)
    plan = UnionNode(left=g_v, right=g_w)
    env = {"T__ins": fact}

    fused_plan, fused_env = fuse_delta_groupbys(plan, env)
    spliced = [n for n in fused_env if n.startswith("__fused__")]
    assert len(spliced) == 2, spliced  # distinct names, no overwrite

    got = to_host(execute(fused_plan, fused_env))
    want = to_host(execute(plan, env))
    ga = dict(zip(got["g"].tolist(), got["a"].tolist()))
    wa = dict(zip(want["g"].tolist(), want["a"].tolist()))
    assert ga == wa  # union keeps the LEFT (sum of v) aggregates


def test_fused_dispatch_falls_back_on_nonfusable_plan():
    """Views whose delta aggregation is not groupby-sum/count over η-filtered
    rows (here: mean agg) take the plan-executor path under fused=True."""
    from repro.core import ViewDef
    from repro.core.maintenance import cleaning_plan, _match_fused_groupby
    from repro.data.synthetic import make_log_video
    from repro.relational.plan import FKJoin, GroupByNode, Scan

    rng = np.random.default_rng(2)
    log, video = make_log_video(rng, 100, 1000)
    plan = GroupByNode(
        child=FKJoin(fact=Scan("Log", pk=("sessionId",)),
                     dim=Scan("Video", pk=("videoId",)), fact_key="videoId"),
        keys=("videoId",),
        aggs=(("avgBytes", "mean", "bytes"),),
        num_groups=256,
    )
    cp = cleaning_plan(plan, ("videoId",), 0.2, 5)

    def walk(p):
        import dataclasses as dc
        from repro.relational.plan import Plan

        found = _match_fused_groupby(p, {"Log": log, "Video": video})
        if found is not None:
            return [found]
        out = []
        for f in dc.fields(p):
            v = getattr(p, f.name)
            if isinstance(v, Plan):
                out.extend(walk(v))
        return out

    assert walk(cp) == []  # nothing fusable: mean is not sum/count


# ---------------------------------------------------------------------------
# outlier_member: fused η ∨ digest membership (§6.2 skew fast path)
# ---------------------------------------------------------------------------

from repro.core.hashing import key_digest
from repro.kernels.outlier_member import fused_hash_member, outlier_member
from repro.kernels.outlier_member.ref import fused_hash_member_ref, member_digest_ref


def _member_scenario(rng, n, k, ncols):
    from repro.relational.relation import SENTINEL_KEY

    keys = tuple(jnp.asarray(rng.integers(0, 400, k).astype(np.int32))
                 for _ in range(ncols))
    probe = [rng.integers(0, 400, n).astype(np.int32) for _ in range(ncols)]
    hits = rng.integers(0, k, max(1, n // 8))
    for c in range(ncols):
        probe[c][: len(hits)] = np.asarray(keys[c])[hits]
    probe[0][-1] = SENTINEL_KEY  # sentinel probe row never matches
    return tuple(jnp.asarray(p) for p in probe), keys


@pytest.mark.parametrize("n", [1, 255, 256, 4096, 5001])
@pytest.mark.parametrize("k", [1, 64, 257])
@pytest.mark.parametrize("ncols", [1, 2, 3])
def test_outlier_member_kernel_sweep(n, k, ncols):
    """Pallas kernel == XLA binary-search path == dense oracle."""
    rng = np.random.default_rng(n * 13 + k + ncols)
    probe, keys = _member_scenario(rng, n, k, ncols)
    khi, klo = key_digest(keys)
    want = np.asarray(member_digest_ref(probe, khi, klo))
    got_xla = np.asarray(outlier_member(probe, keys, use_pallas=False))
    got_pal = np.asarray(outlier_member(probe, keys, use_pallas=True))
    assert np.array_equal(got_xla, want)
    assert np.array_equal(got_pal, want)


@pytest.mark.parametrize("m", [0.0, 0.3, 1.0])
def test_fused_hash_member_matches_composed_oracles(m):
    """keep == η-oracle ∨ member-oracle bit-for-bit on both paths."""
    rng = np.random.default_rng(int(m * 10) + 3)
    probe, keys = _member_scenario(rng, 3000, 128, 2)
    khi, klo = key_digest(keys)
    want_keep, want_mem = fused_hash_member_ref(probe, m, 11, khi, klo)
    for up in (False, True):
        keep, mem = fused_hash_member(probe, m, 11, keys, use_pallas=up)
        assert np.array_equal(np.asarray(keep), np.asarray(want_keep)), up
        assert np.array_equal(np.asarray(mem), np.asarray(want_mem)), up


def test_outlier_member_match_in_last_table_slot():
    """Regression: the binary-search descent must reach index K−1."""
    keys = (jnp.asarray(np.arange(64, dtype=np.int32)),
            jnp.zeros(64, jnp.int32))
    khi, _ = key_digest(keys)
    last_key = int(np.argmax(np.asarray(khi)))  # sorts to the last slot
    probe = (jnp.asarray(np.array([last_key], np.int32)), jnp.zeros(1, jnp.int32))
    assert bool(np.asarray(outlier_member(probe, keys, use_pallas=False))[0])
    assert bool(np.asarray(outlier_member(probe, keys, use_pallas=True))[0])


# ---------------------------------------------------------------------------
# flash attention (the §Roofline memory-term lever)
# ---------------------------------------------------------------------------

import jax.numpy as _jnp

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_ref


@pytest.mark.parametrize("shape", [(2, 128, 4, 4, 64), (1, 300, 8, 2, 32),
                                   (2, 256, 4, 1, 128), (1, 64, 2, 2, 16)])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_flash_attention_sweep(shape, dtype):
    B, S, H, K, hd = shape
    rng = np.random.default_rng(S + H)
    dt = _jnp.bfloat16 if dtype == "bfloat16" else _jnp.float32
    q = _jnp.asarray(rng.normal(size=(B, S, H, hd)), dt)
    k = _jnp.asarray(rng.normal(size=(B, S, K, hd)), dt)
    v = _jnp.asarray(rng.normal(size=(B, S, K, hd)), dt)
    got = np.asarray(flash_attention(q, k, v), np.float32)
    kr = _jnp.repeat(k, H // K, 2)
    vr = _jnp.repeat(v, H // K, 2)
    want = np.asarray(flash_ref(
        _jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd),
        _jnp.moveaxis(kr, 2, 1).reshape(B * H, S, hd),
        _jnp.moveaxis(vr, 2, 1).reshape(B * H, S, hd)), np.float32)
    want = np.moveaxis(want.reshape(B, H, S, hd), 1, 2)
    tol = 2e-3 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_flash_attention_matches_model_attention():
    """Flash kernel ≡ the model's chunked_attention (causal GQA)."""
    from repro.models.layers import gqa_attention, causal_mask

    rng = np.random.default_rng(3)
    B, S, H, K, hd = 2, 128, 4, 2, 32
    q = _jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    k = _jnp.asarray(rng.normal(size=(B, S, K, hd)).astype(np.float32))
    v = _jnp.asarray(rng.normal(size=(B, S, K, hd)).astype(np.float32))
    got = np.asarray(flash_attention(q, k, v))
    want = np.asarray(gqa_attention(q, k, v, causal_mask(S, S)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# multi_agg: batched-query moment kernel
# ---------------------------------------------------------------------------

from repro.kernels.multi_agg import multi_agg_moments
from repro.kernels.multi_agg.ref import multi_agg_ref


def _random_panel(rng, R, C):
    x = _jnp.asarray(rng.normal(10.0, 4.0, (R, C)).astype(np.float32))
    valid = _jnp.asarray(rng.uniform(size=R) < 0.8)
    pin = rng.uniform(size=R) < 0.1
    m = 0.25
    w = _jnp.asarray(np.where(pin, 1.0, 1.0 / m).astype(np.float32))
    ompi = _jnp.asarray(np.where(pin, 0.0, 1.0 - m).astype(np.float32))
    return x, valid, w, ompi


def _random_batch(rng, C, Q, P):
    """Random encoded sel/meta tables (see repro.query.batch layout)."""
    sel = np.zeros(((1 + P) * C, Q), np.float32)
    meta = np.zeros((2 + 4 * P, Q), np.float32)
    meta[2::4, :] = -np.inf
    meta[3::4, :] = -np.inf
    meta[4::4, :] = np.inf
    meta[5::4, :] = np.inf
    for q in range(Q):
        op = rng.integers(0, 3)
        if op == 1:
            meta[0, q] = 1.0  # count
        else:
            sel[rng.integers(0, C), q] = 1.0
            if op == 2:
                meta[1, q] = 1.0  # avg
        for p in range(rng.integers(0, P + 1)):
            sel[(1 + p) * C + rng.integers(0, C), q] = 1.0
            lo = rng.normal(8.0, 3.0)
            meta[2 + 4 * p, q] = lo
            meta[4 + 4 * p, q] = lo + abs(rng.normal(0, 6.0))
    return _jnp.asarray(sel), _jnp.asarray(meta)


# (sides, R, C, Q, P, all-invalid side): C = 1, 4, 6, 13 give slabs of
# S = 8, 8, 16, 16 sublanes; R from below one lane tile to several tiles
# with a ragged last one (9000 rows at Q = 8 and 2500 at Q = 130)
_MULTI_AGG_CASES = [
    (2, 64, 2, 3, 1, None),
    (2, 300, 5, 9, 2, None),
    (2, 1024, 3, 17, 1, None),
    (1, 100, 4, 5, 1, None),
    (1, 513, 2, 12, 2, None),
    (2, 64, 1, 1, 0, None),
    (2, 9000, 4, 8, 1, None),
    (2, 700, 6, 9, 2, "old"),
    (2, 2500, 13, 130, 2, None),
    (1, 300, 1, 8, 0, None),
    (1, 9000, 13, 9, 1, None),
    (1, 200, 6, 130, 1, "new"),
]


@pytest.mark.parametrize(
    "sides,R,C,Q,P,invalid", _MULTI_AGG_CASES,
    ids=[f"s{c[0]}-R{c[1]}-C{c[2]}-Q{c[3]}-P{c[4]}" + (f"-{c[5]}0" if c[5] else "")
         for c in _MULTI_AGG_CASES],
)
def test_multi_agg_kernel_matches_ref(sides, R, C, Q, P, invalid):
    rng = np.random.default_rng(R * 7 + Q * 3 + C + sides)
    xn, vn, wn, on = _random_panel(rng, R, C)
    xo, vo, wo, oo = _random_panel(rng, R, C)
    if invalid == "new":
        vn = _jnp.zeros_like(vn)
    if invalid == "old":
        vo = _jnp.zeros_like(vo)
    sel, meta = _random_batch(rng, C, Q, P)
    old = (xo, vo, wo, oo) if sides == 2 else ()
    want = np.asarray(multi_agg_ref(xn, vn, wn, on, sel, meta, *old))
    got = np.asarray(multi_agg_moments(xn, vn, wn, on, sel, meta, *old,
                                       use_pallas=True))
    assert got.shape == (12, Q)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-2)


@pytest.mark.parametrize("sides", [1, 2])
def test_multi_agg_bounds_at_non_bf16_values_are_exact(sides):
    """A range bound equal to a column value that bfloat16 cannot hold keeps
    or drops exactly the rows the reference does: the column select must
    hand the predicate its f32 value unrounded."""
    from repro.kernels.multi_agg import K_NEW, K_OLD, S_NEW

    R, C, Q = 1000, 4, 8
    rng = np.random.default_rng(11 + sides)
    edge = np.float32(1.0 + 2.0 ** -20)  # rounds to 1.0 in bf16
    x = rng.choice(np.array([1.0, edge, 1.0 + 2.0 ** -19], np.float32),
                   (R, C)).astype(np.float32)
    valid = _jnp.asarray(rng.uniform(size=R) < 0.9)
    w = _jnp.ones(R, _jnp.float32)
    ompi = _jnp.zeros(R, _jnp.float32)
    sel = np.zeros((2 * C, Q), np.float32)
    meta = np.zeros((6, Q), np.float32)
    meta[2:4], meta[4:6] = -np.inf, np.inf
    for q in range(Q):
        meta[1, q] = q % 2  # avg (its count is the kept rows), else sum
        sel[q % C, q] = 1.0
        sel[C + (q // 2) % C, q] = 1.0
        meta[2 + q % 4, q] = edge  # ge, gt, le, lt = the edge value
    args = (_jnp.asarray(x), valid, w, ompi, _jnp.asarray(sel), _jnp.asarray(meta))
    old = args[:4] if sides == 2 else ()
    want = np.asarray(multi_agg_ref(*args, *old))
    got = np.asarray(multi_agg_moments(*args, *old, use_pallas=True))
    rows = [K_NEW, K_OLD] if sides == 2 else [K_NEW]
    np.testing.assert_array_equal(got[rows], want[rows])
    np.testing.assert_allclose(got[S_NEW], want[S_NEW], rtol=1e-6)
    # the edge rows are what separates the bounds: kept by ge/le, dropped by gt/lt
    assert len(set(want[K_NEW, 1::2].tolist())) > 1


def test_multi_agg_ht_d_excludes_pinned_rows():
    """HT_D weights d² by min(1−π_new, 1−π_old): rows pinned by the outlier
    index on either side (ompi = 0) contribute nothing; with no pins at all
    HT_D reduces to the seed's (1−m)·SS_D."""
    from repro.kernels.multi_agg import HT_D, SS_D

    rng = np.random.default_rng(5)
    R, C = 300, 3
    m = 0.25
    x_new, vn, _, _ = _random_panel(rng, R, C)
    x_old, vo, _, _ = _random_panel(rng, R, C)
    pin_new = rng.uniform(size=R) < 0.15
    pin_old = pin_new.copy()
    pin_old[:10] = ~pin_old[:10]  # a few one-sided pins too
    wn = _jnp.asarray(np.where(pin_new, 1.0, 1.0 / m).astype(np.float32))
    wo = _jnp.asarray(np.where(pin_old, 1.0, 1.0 / m).astype(np.float32))
    on = _jnp.asarray(np.where(pin_new, 0.0, 1.0 - m).astype(np.float32))
    oo = _jnp.asarray(np.where(pin_old, 0.0, 1.0 - m).astype(np.float32))
    sel, meta = _random_batch(rng, C, 6, 1)

    for up in (False, True):
        mom = np.asarray(multi_agg_moments(x_new, vn, wn, on, sel, meta,
                                           x_old, vo, wo, oo, use_pallas=up))
        from repro.kernels.multi_agg.ref import _trans_table

        tn, _ = _trans_table(x_new, vn.astype(bool), wn, sel, meta)
        to, _ = _trans_table(x_old, vo.astype(bool), wo, sel, meta)
        d = np.asarray(tn - to)
        od = np.minimum(np.asarray(on), np.asarray(oo))[:, None]
        want_htd = (od * d * d).sum(axis=0)
        np.testing.assert_allclose(mom[HT_D], want_htd, rtol=2e-5, atol=1e-2)
        # pinned-both-sides rows are excluded even where d != 0
        both = pin_new & pin_old
        assert (np.abs(d[both]).sum() > 0) or not both.any()

    # no pins anywhere ⇒ HT_D == (1−m)·SS_D exactly
    ones_w = _jnp.full(R, 1.0 / m, _jnp.float32)
    ompi = _jnp.full(R, 1.0 - m, _jnp.float32)
    mom0 = np.asarray(multi_agg_moments(x_new, vn, ones_w, ompi, sel, meta,
                                        x_old, vo, ones_w, ompi, use_pallas=False))
    np.testing.assert_allclose(mom0[HT_D], (1.0 - m) * mom0[SS_D], rtol=2e-5, atol=1e-2)


# ---------------------------------------------------------------------------
# kernels/fleet_score: the planner's one-pass fleet scorer
# ---------------------------------------------------------------------------

def _random_fleet_features(rng, V):
    from repro.kernels.fleet_score import (
        F_AGE, F_COST_CLEAN, F_COST_MAINTAIN, F_COST_RETUNE, F_DRIFT_CLEAN,
        F_DRIFT_IVM, F_EX2, F_HT_AQP, F_HT_CORR, F_M, F_MEAN, F_N, F_TRAFFIC,
        N_FEATURES,
    )

    f = np.zeros((V, N_FEATURES), np.float32)
    f[:, F_N] = rng.uniform(10, 1e4, V)
    f[:, F_EX2] = rng.uniform(0.1, 500, V)
    f[:, F_MEAN] = rng.uniform(-20, 20, V)
    f[:, F_HT_AQP] = rng.uniform(0, 1e5, V)
    f[:, F_HT_CORR] = rng.uniform(0, 1e5, V)
    f[:, F_DRIFT_CLEAN] = rng.integers(0, 2000, V)
    f[:, F_DRIFT_IVM] = rng.integers(0, 4000, V)
    f[:, F_TRAFFIC] = rng.uniform(0, 100, V)
    f[:, F_COST_CLEAN] = rng.uniform(1e-3, 2.0, V)
    f[:, F_COST_MAINTAIN] = rng.uniform(1e-2, 10.0, V)
    f[:, F_COST_RETUNE] = rng.uniform(2e-3, 4.0, V)
    f[:, F_AGE] = rng.uniform(0, 1e3, V)
    f[:, F_M] = rng.uniform(0.01, 1.0, V)
    return f


@pytest.mark.parametrize("V", [1, 5, 37, 513])
def test_fleet_score_kernel_matches_oracle(V):
    """Pallas tile pass == pure-jnp oracle == XLA path (f32 ulp jitter)."""
    from repro.kernels.fleet_score import fleet_score_ref
    from repro.kernels.fleet_score.ops import fleet_scores

    rng = np.random.default_rng(V)
    feats = _random_fleet_features(rng, V)
    want = np.asarray(fleet_score_ref(feats))
    got_xla = np.asarray(fleet_scores(feats, use_pallas=False))
    got_pl = np.asarray(fleet_scores(feats, use_pallas=True))
    from repro.kernels.fleet_score import N_SCORES

    assert got_pl.shape == (V, N_SCORES)
    np.testing.assert_allclose(got_xla, want, rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(got_pl, want, rtol=2e-6, atol=1e-6)


def test_fleet_score_degenerate_views_score_zero():
    """All-zero feature rows (padding, empty views) must score 0 on every
    action — no NaN/Inf leaks from the guarded divisors — and recommend no
    ratio change (REC_M 0 for zero-m lanes)."""
    from repro.kernels.fleet_score import N_FEATURES, REC_M
    from repro.kernels.fleet_score.ops import fleet_scores

    feats = np.zeros((3, N_FEATURES), np.float32)
    for up in (False, True):
        got = np.asarray(fleet_scores(feats, use_pallas=up))
        assert np.all(np.isfinite(got))
        np.testing.assert_array_equal(got[:, :4], 0.0)
        np.testing.assert_array_equal(got[:, REC_M], 0.0)


def test_fleet_score_recommended_m_steps_and_clamps():
    """REC_M steps the ratio by ×/÷M_STEP when the canonical total's
    relative standard error leaves the band, holds inside it, and clamps
    at the [M_MIN, M_MAX] bounds."""
    from repro.kernels.fleet_score import (
        F_HT_AQP, F_M, F_MEAN, F_N, M_MAX, M_MIN, M_STEP, N_FEATURES, REC_M,
    )
    from repro.kernels.fleet_score.ops import fleet_scores

    def rec(m, rel_se, up):
        f = np.zeros((1, N_FEATURES), np.float32)
        f[0, F_N], f[0, F_MEAN], f[0, F_M] = 100.0, 10.0, m
        f[0, F_HT_AQP] = (rel_se * 1000.0) ** 2
        return float(np.asarray(fleet_scores(f, use_pallas=up))[0, REC_M])

    for up in (False, True):
        assert rec(0.25, 0.05, up) == pytest.approx(0.25 * M_STEP)  # noisy
        assert rec(0.25, 0.001, up) == pytest.approx(0.25 / M_STEP)  # over
        assert rec(0.25, 0.01, up) == pytest.approx(0.25)  # in band
        assert rec(M_MAX, 0.05, up) == pytest.approx(M_MAX)  # clamp high
        assert rec(M_MIN, 0.001, up) == pytest.approx(M_MIN)  # clamp low
        # zero sampling variance (m = 1 / all-pinned / empty) is no signal:
        # hold, don't step down (an m = 1 view must not oscillate 1 ⇄ 0.5)
        assert rec(1.0, 0.0, up) == pytest.approx(1.0)
        assert rec(0.25, 0.0, up) == pytest.approx(0.25)
        # an m below M_MIN is never yanked to the bound: over-sampling
        # evidence holds (a step down can't go further), noise steps up
        # toward the band, and in-band recommends exactly m (no clip)
        assert rec(M_MIN / 2, 0.001, up) == pytest.approx(M_MIN / 2)
        assert rec(M_MIN / 2, 0.05, up) == pytest.approx(M_MIN)
        assert rec(M_MIN / 2, 0.01, up) == pytest.approx(M_MIN / 2)


# ---------------------------------------------------------------------------
# kernels/fleet_moments: the fleet panel's batched snapshot pass
# ---------------------------------------------------------------------------

def _random_fleet_panel(rng, V, R, ragged=True):
    """Eight (V, R) channels with per-view ragged lengths, outlier-pinned
    rows (w = 1, ompi = 0), and the all-zero padding contract."""
    chans = []
    rows = rng.integers(0, R + 1, V) if ragged else np.full(V, R)
    for _side in range(2):
        live = np.arange(R)[None, :] < rows[:, None]
        v = ((rng.random((V, R)) < 0.8) & live).astype(np.float32)
        x = np.where(v > 0, rng.normal(0, 5, (V, R)), 0.0).astype(np.float32)
        pin = (rng.random((V, R)) < 0.15) & (v > 0)
        w = np.where(pin, 1.0, 4.0).astype(np.float32) * (live > 0)
        o = np.where(pin, 0.0, 0.75).astype(np.float32) * (live > 0)
        chans += [x, v, w.astype(np.float32), o.astype(np.float32)]
    return chans


@pytest.mark.parametrize("V,R", [(1, 64), (7, 300), (12, 1024), (130, 96)])
def test_fleet_moments_kernel_matches_oracle(V, R):
    """Pallas tile pass == pure-jnp oracle == XLA path over ragged fleets."""
    from repro.kernels.fleet_moments import N_MOMENTS, fleet_moments, fleet_moments_ref

    rng = np.random.default_rng(V * 1000 + R)
    chans = _random_fleet_panel(rng, V, R)
    want = np.asarray(fleet_moments_ref(*chans))
    got_xla = np.asarray(fleet_moments(*chans, use_pallas=False))
    got_pl = np.asarray(fleet_moments(*chans, use_pallas=True))
    assert got_pl.shape == (V, N_MOMENTS)
    np.testing.assert_allclose(got_xla, want, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(got_pl, want, rtol=1e-5, atol=1e-3)


def test_fleet_moments_zero_padding_contributes_nothing():
    """All-zero rows and views (the panel's padding contract) reduce to
    exactly zero in every moment, on both dispatch paths."""
    from repro.kernels.fleet_moments import fleet_moments

    rng = np.random.default_rng(3)
    chans = _random_fleet_panel(rng, 4, 200, ragged=False)
    padded = [np.pad(c, ((0, 2), (0, 120))) for c in chans]
    for up in (False, True):
        base = np.asarray(fleet_moments(*chans, use_pallas=up))
        grown = np.asarray(fleet_moments(*padded, use_pallas=up))
        np.testing.assert_allclose(grown[:4], base, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(grown[4:], 0.0)


def test_fused_clean_groupby_fleet_matches_per_view():
    """The batched fleet delta aggregation equals per-view
    fused_clean_groupby for every member (per-view seeds and ratios)."""
    from repro.kernels.fused_clean.ops import (
        fused_clean_groupby,
        fused_clean_groupby_fleet,
    )

    rng = np.random.default_rng(11)
    V, R, C, G = 5, 400, 2, 64
    gid = rng.integers(0, G, (V, R)).astype(np.int32)
    vals = rng.normal(0, 3, (V, R, C)).astype(np.float32)
    valid = rng.random((V, R)) < 0.9
    ms = (0.25, 0.5, 0.125, 1.0, 0.25)
    seeds = (0, 1, 2, 3, 40)
    counts, sums = fused_clean_groupby_fleet(
        gid, vals, valid, ms=ms, seeds=seeds, num_groups=G
    )
    for v in range(V):
        c1, s1 = fused_clean_groupby(
            gid[v], vals[v], valid[v], m=ms[v], seed=seeds[v], num_groups=G,
            use_pallas=False,
        )
        np.testing.assert_allclose(np.asarray(counts)[v], np.asarray(c1),
                                   rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(sums)[v], np.asarray(s1),
                                   rtol=1e-6, atol=1e-4)


# ---------------------------------------------------------------------------
# kernels/fleet_merge: the epoch's one-pass batched clean merge
# ---------------------------------------------------------------------------

def _random_merge_fleet(rng, V, R, G, A, with_del=True, stale_rows=None):
    """Padded merge panels: ragged stale rows with unique keys (some beyond
    the delta group range, so they must pass through untouched) and dense
    delta sides with random group liveness."""
    from repro.relational.relation import SENTINEL_KEY

    rows = (np.asarray(stale_rows) if stale_rows is not None
            else rng.integers(0, R + 1, V))
    sk = np.full((V, R), SENTINEL_KEY, np.int32)
    sv = np.zeros((V, R), bool)
    sx = np.zeros((V, R, A), np.float32)
    hi = G + G // 2 + 1
    for v in range(V):
        n = int(min(rows[v], hi))
        if n:
            sk[v, :n] = rng.choice(hi, size=n, replace=False)
            sv[v, :n] = True
            sx[v, :n] = rng.normal(0, 5, (n, A)).astype(np.float32)
    iv = rng.random((V, G)) < 0.5
    ix = np.where(iv[..., None],
                  rng.normal(0, 3, (V, G, A)), 0.0).astype(np.float32)
    if not with_del:
        return sk, sv, sx, iv, ix, None, None
    dv = rng.random((V, G)) < 0.3
    dx = np.where(dv[..., None],
                  rng.normal(0, 2, (V, G, A)), 0.0).astype(np.float32)
    return sk, sv, sx, iv, ix, dv, dx


def _merge_oracle(sk, sv, sx, iv, ix, dv, dx):
    """Per-view numpy dict merge in the op's f32 order: (stale + ins) − del
    per aggregate, delta-only groups appended, rows sorted by key."""
    V, R = sk.shape
    G = iv.shape[1]
    A = sx.shape[2]
    if dv is None:
        dv = np.zeros((V, G), bool)
        dx = np.zeros((V, G, A), np.float32)
    keys_out, vals_out = [], []
    for v in range(V):
        rows = {}
        for r in range(R):
            if not sv[v, r]:
                continue
            k = int(sk[v, r])
            val = sx[v, r].astype(np.float32)
            if 0 <= k < G:
                if iv[v, k]:
                    val = (val + ix[v, k]).astype(np.float32)
                if dv[v, k]:
                    val = (val - dx[v, k]).astype(np.float32)
            rows[k] = val
        for g in range(G):
            if g in rows or not (iv[v, g] or dv[v, g]):
                continue
            val = ix[v, g].copy() if iv[v, g] else np.zeros(A, np.float32)
            if dv[v, g]:
                val = (val - dx[v, g]).astype(np.float32)
            rows[g] = val
        ks = sorted(rows)
        keys_out.append(np.asarray(ks, np.int64))
        vals_out.append(np.asarray([rows[k] for k in ks], np.float32)
                        if ks else np.zeros((0, A), np.float32))
    return keys_out, vals_out


def _check_merge_against_oracle(panels):
    from repro.kernels.fleet_merge import fleet_merge

    want_k, want_x = _merge_oracle(*panels)
    sk, sv, sx, iv, ix, dv, dx = panels
    outs = {}
    for up in (False, True):
        keys, vals, valid = fleet_merge(sk, sv, sx, iv, ix, dv, dx,
                                        use_pallas=up)
        keys, vals, valid = map(np.asarray, (keys, vals, valid))
        assert keys.shape == (sk.shape[0], sk.shape[1] + iv.shape[1])
        for v in range(sk.shape[0]):
            n = len(want_k[v])
            assert valid[v, :n].all() and not valid[v, n:].any()
            np.testing.assert_array_equal(keys[v, :n], want_k[v])
            np.testing.assert_allclose(vals[v, :n], want_x[v],
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(vals[v, n:], 0.0)
        outs[up] = (keys, vals, valid)
    # the two dispatch paths agree bit-for-bit (same f32 operation order)
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    np.testing.assert_array_equal(outs[False][1], outs[True][1])
    np.testing.assert_array_equal(outs[False][2], outs[True][2])


@pytest.mark.parametrize("V,R,G", [(1, 17, 32), (5, 300, 64),
                                   (9, 513, 128), (3, 1, 8)])
def test_fleet_merge_matches_oracle(V, R, G):
    """Pallas == XLA == per-view dict oracle over ragged fleets with
    deletes — including V=1 fleets and single-row (R=1) stale buckets."""
    rng = np.random.default_rng(V * 1000 + R + G)
    _check_merge_against_oracle(_random_merge_fleet(rng, V, R, G, A=2))


def test_fleet_merge_sorted_keys_visit_only_their_slabs():
    """Sorted stale keys over a wide group domain (the fleet panel's
    layout): each row tile visits only the group slabs its key span covers,
    padding tiles visit none, and the result still equals the oracle with
    both paths bit-equal."""
    from repro.kernels.fleet_merge.kernel import BLOCK_G, BLOCK_R
    from repro.kernels.fleet_merge.ops import _slab_ranges

    rng = np.random.default_rng(29)
    V, R, G = 2, 3000, 4096
    panels = _random_merge_fleet(rng, V, R, G, A=2, stale_rows=[2500, 900])
    sk, sv = panels[0], panels[1]
    for v in range(V):
        n = int(sv[v].sum())
        sk[v, :n] = np.sort(sk[v, :n])
    rp = -(-R // BLOCK_R) * BLOCK_R
    keys_t = np.pad(np.where(sv, sk, np.iinfo(np.int32).max),
                    ((0, 128 - V), (0, rp - R)),
                    constant_values=np.iinfo(np.int32).max).T
    slab0, nslab = map(np.asarray, _slab_ranges(jnp.asarray(keys_t), G))
    assert nslab.max() < G // BLOCK_G  # tiles skip most of the domain
    assert nslab[-1] == 0  # the last tile is padding only
    _check_merge_against_oracle(panels)


def test_fleet_merge_insert_only_path():
    """No delete side (views without with_deletes): del panels default to
    all-dead and the merge reduces to a pure upsert."""
    rng = np.random.default_rng(7)
    _check_merge_against_oracle(
        _random_merge_fleet(rng, 4, 96, 64, A=3, with_del=False))


def test_fleet_merge_all_delete_deltas():
    """A micro-batch that is ALL deletes cancels into the stale rows and
    spawns negative delta-only groups — both paths, exactly."""
    rng = np.random.default_rng(13)
    sk, sv, sx, iv, ix, dv, dx = _random_merge_fleet(rng, 3, 40, 32, A=2)
    iv[:] = False
    ix[:] = 0.0
    dv = rng.random(dv.shape) < 0.6
    dx = np.where(dv[..., None],
                  rng.normal(0, 2, dx.shape), 0.0).astype(np.float32)
    _check_merge_against_oracle((sk, sv, sx, iv, ix, dv, dx))


def test_fleet_merge_all_padding_slots():
    """A fleet of all-padding slots (zero valid stale rows, dead deltas)
    comes back entirely invalid: SENTINEL keys, zero values, both paths."""
    from repro.kernels.fleet_merge import fleet_merge
    from repro.relational.relation import SENTINEL_KEY

    rng = np.random.default_rng(5)
    sk, sv, sx, iv, ix, dv, dx = _random_merge_fleet(
        rng, 4, 64, 32, A=2, stale_rows=np.zeros(4, int))
    iv[:] = False
    dv[:] = False
    for up in (False, True):
        keys, vals, valid = fleet_merge(sk, sv, sx, iv, ix, dv, dx,
                                        use_pallas=up)
        assert not np.asarray(valid).any()
        np.testing.assert_array_equal(np.asarray(keys), SENTINEL_KEY)
        np.testing.assert_array_equal(np.asarray(vals), 0.0)


def test_fleet_merge_raises_on_ragged_shapes():
    from repro.kernels.fleet_merge import fleet_merge

    rng = np.random.default_rng(3)
    sk, sv, sx, iv, ix, dv, dx = _random_merge_fleet(rng, 2, 16, 8, A=2)
    with pytest.raises(ValueError):
        fleet_merge(sk, sv[:, :-1], sx, iv, ix, dv, dx)
    with pytest.raises(ValueError):
        fleet_merge(sk, sv, sx, iv[:1], ix, dv, dx)
