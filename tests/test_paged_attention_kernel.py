"""Pallas paged-decode kernel (kernels/paged_attention.py).

Reference capability: the decode branch of
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu —
one query row per slot attending over that slot's paged KV window via
the block table. Load-bearing checks:

- kernel output == dense per-slot oracle at f32 over random lens
  (partial pages, GQA fold, per-slot windows),
- int8 pools with per-page-per-head scales dequantize inside the
  kernel to match the dequantized oracle,
- shape contract: forced-but-impossible geometry raises a ValueError
  naming the misaligned dims (ring_attention_local(use_flash=True)
  contract),
- the kernel jits and scans (the engine's tick wraps it in lax.scan).

All on CPU via interpret=True — the same mode the engine uses off-TPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.paged_attention import (_VMEM_BUDGET,
                                                check_decode_shapes,
                                                decode_plan,
                                                decode_shape_problems,
                                                paged_decode_attention,
                                                paged_kv_write,
                                                pages_by_head,
                                                pool_rows_shape)


def _setup(b=3, hq=4, hk=2, d=8, ps=4, npages=16, mp=4, seed=0):
    rng = np.random.default_rng(seed)
    kp = rng.normal(size=(npages, hk, ps, d)).astype(np.float32)
    vp = rng.normal(size=(npages, hk, ps, d)).astype(np.float32)
    bt = np.zeros((b, mp), np.int32)
    page = 1
    for i in range(b):
        for j in range(mp):
            bt[i, j] = page
            page += 1
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    lens = rng.integers(0, mp * ps, size=b).astype(np.int32)
    return q, kp, vp, bt, lens


def _oracle(q, kd, vd, bt, lens):
    """Dense per-slot attention over the dequantized window."""
    b, hq, d = q.shape
    hk = kd.shape[1]
    g = hq // hk
    out = np.zeros((b, hq, d), np.float32)
    for i in range(b):
        L = int(lens[i]) + 1
        ks = np.concatenate([kd[bt[i, j]] for j in range(bt.shape[1])],
                            axis=1)          # (hk, mp*ps, d)
        vs = np.concatenate([vd[bt[i, j]] for j in range(bt.shape[1])],
                            axis=1)
        for h in range(hq):
            kh, vh = ks[h // g][:L], vs[h // g][:L]
            sc = q[i, h] @ kh.T / np.sqrt(d)
            p = np.exp(sc - sc.max())
            p /= p.sum()
            out[i, h] = p @ vh
    return out


def _quant(pool):
    s = np.abs(pool).max(axis=(2, 3)) / 127.0            # (npages, hk)
    qp = np.clip(np.round(pool / np.maximum(s[:, :, None, None], 1e-30)),
                 -127, 127).astype(np.int8)
    return qp, s.astype(np.float32)


def test_kernel_matches_dense_oracle_f32():
    q, kp, vp, bt, lens = _setup()
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(lens), interpret=True))
    np.testing.assert_allclose(out, _oracle(q, kp, vp, bt, lens),
                               rtol=2e-5, atol=2e-5)


def test_kernel_no_gqa_and_len_zero():
    # hq == hk (g=1) and a slot whose window is a single position
    q, kp, vp, bt, lens = _setup(hq=2, hk=2)
    lens[0] = 0
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(lens), interpret=True))
    np.testing.assert_allclose(out, _oracle(q, kp, vp, bt, lens),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(out).all()


def test_kernel_int8_dequant_in_kloop():
    q, kp, vp, bt, lens = _setup(seed=3)
    kq, ks = _quant(kp)
    vq, vs = _quant(vp)
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
        jnp.asarray(bt), jnp.asarray(lens),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs),
        interpret=True))
    kd = kq.astype(np.float32) * ks[:, :, None, None]
    vd = vq.astype(np.float32) * vs[:, :, None, None]
    np.testing.assert_allclose(out, _oracle(q, kd, vd, bt, lens),
                               rtol=1e-4, atol=1e-4)
    # quantization is lossy but close: vs the unquantized oracle the
    # error is bounded by the int8 step, not garbage
    ref = _oracle(q, kp, vp, bt, lens)
    assert np.max(np.abs(out - ref)) < 0.2


def test_kernel_int8_requires_scales():
    q, kp, vp, bt, lens = _setup()
    with pytest.raises(ValueError, match="k_scale"):
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp).astype(jnp.int8),
            jnp.asarray(vp).astype(jnp.int8), jnp.asarray(bt),
            jnp.asarray(lens), interpret=True)


def test_shape_contract_names_misaligned_dims():
    # hq not a multiple of hk: rejected even in interpret mode
    with pytest.raises(ValueError, match=r"hq=3, hk=2"):
        check_decode_shapes(3, 2, 8, 4, interpret=True)
    # compiled-TPU-only constraints named when interpret=False
    with pytest.raises(ValueError, match=r"head_dim % 8"):
        check_decode_shapes(4, 2, 6, 8, interpret=False)
    with pytest.raises(ValueError, match=r"page_size % 8"):
        check_decode_shapes(4, 2, 8, 4, interpret=False)
    # the auto-gate sees the same reasons without raising
    assert decode_shape_problems(3, 2, 8, 4, interpret=True)
    assert not decode_shape_problems(4, 2, 8, 4, interpret=True)
    assert not decode_shape_problems(4, 2, 128, 16, interpret=False)
    # compiled sublane tile is POOL-dtype dependent: int8 needs
    # page_size % 32, bf16 % 16, f32 % 8 — interpret mode doesn't care
    assert decode_shape_problems(4, 2, 128, 16, interpret=False,
                                 kv_dtype="int8")
    assert not decode_shape_problems(4, 2, 128, 32, interpret=False,
                                     kv_dtype="int8")
    assert decode_shape_problems(4, 2, 128, 8, interpret=False,
                                 kv_dtype="bfloat16")
    assert not decode_shape_problems(4, 2, 128, 16, interpret=False,
                                     kv_dtype="bfloat16")
    assert not decode_shape_problems(4, 2, 128, 16, interpret=True,
                                     kv_dtype="int8")
    with pytest.raises(ValueError, match=r"page_size % 32.*int8"):
        check_decode_shapes(4, 2, 128, 16, interpret=False,
                            kv_dtype="int8")


def test_kernel_under_jit_and_scan():
    q, kp, vp, bt, lens = _setup(b=2, mp=3, npages=8)

    @jax.jit
    def run(qa, kpa, vpa):
        def step(carry, _):
            o = paged_decode_attention(qa, kpa, vpa, jnp.asarray(bt),
                                       jnp.asarray(lens),
                                       interpret=True)
            return carry, o
        _, outs = jax.lax.scan(step, 0, jnp.arange(2))
        return outs

    outs = np.asarray(run(jnp.asarray(q), jnp.asarray(kp),
                          jnp.asarray(vp)))
    ref = _oracle(q, kp, vp, bt, lens)
    for t in range(2):
        np.testing.assert_allclose(outs[t], ref, rtol=2e-5, atol=2e-5)


# -- every kv head of 128 keys a grid step (PR 26) ---------------------------

def _paged(b, hq, hk, d, ps, mp, lens, dtype="float32", seed=0):
    """Pools whose slots own the pages their length reaches and point
    every later block-table entry at page 0, the trash page, which is
    poisoned: a kernel that attends (or multiplies by 0) what lies past
    the length shows it. Returns the kernel's arguments and the oracle's
    (rounded through `dtype` and dequantized)."""
    rng = np.random.default_rng(seed)
    npages = b * mp + 1
    kp = rng.normal(size=(npages, hk, ps, d)).astype(np.float32)
    vp = rng.normal(size=(npages, hk, ps, d)).astype(np.float32)
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    lens = np.asarray(lens, np.int32)
    bt = 1 + np.arange(b * mp, dtype=np.int32).reshape(b, mp)
    for i in range(b):
        bt[i, lens[i] // ps + 1:] = 0
    scales = {}
    if dtype == "int8":
        kq, ks = _quant(kp)
        vq, vs = _quant(vp)
        kd = kq.astype(np.float32) * ks[:, :, None, None]
        vd = vq.astype(np.float32) * vs[:, :, None, None]
        kq[0], vq[0] = 127, -127
        ks[0], vs[0] = np.nan, np.inf
        args = [jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq)]
        scales = dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
        qd = q
    else:
        cast = lambda x: jnp.asarray(x).astype(dtype)       # noqa: E731
        back = lambda x: np.array(x.astype(jnp.float32))    # noqa: E731
        kp[0], vp[0] = np.nan, 1e30
        args = [cast(q), cast(kp), cast(vp)]
        qd, kd, vd = (back(a) for a in args)
    kd[0] = vd[0] = 0.0         # the oracle slices past pages off anyway
    args += [jnp.asarray(bt), jnp.asarray(lens)]
    return args, scales, (qd, kd, vd, bt, lens)


_TOL = {"float32": 2e-5, "int8": 1e-4, "bfloat16": 2e-2}

_GEOMETRIES = {
    # the serve cell's heads: full multi-head, d 64, page 16
    "cell_f32": dict(b=3, hq=32, hk=32, d=64, ps=16, mp=13,
                     lens=[5, 100, 207]),
    "cell_bf16": dict(b=3, hq=32, hk=32, d=64, ps=16, mp=13,
                      lens=[77, 128, 191], dtype="bfloat16"),
    # Mistral's: 4 query heads a kv head, d 128
    "gqa4_d128": dict(b=2, hq=16, hk=4, d=128, ps=16, mp=9,
                      lens=[143, 17]),
    "gqa4_d128_bf16": dict(b=2, hq=16, hk=4, d=128, ps=16, mp=9,
                           lens=[130, 64], dtype="bfloat16"),
    "int8_page32": dict(b=3, hq=8, hk=4, d=64, ps=32, mp=5,
                        lens=[0, 70, 159], dtype="int8"),
    "int8_page32_d128": dict(b=2, hq=8, hk=2, d=128, ps=32, mp=7,
                             lens=[33, 223], dtype="int8"),
    # page boundaries on both sides, and a full table
    "page_edges": dict(b=5, hq=4, hk=4, d=64, ps=16, mp=8,
                       lens=[0, 15, 16, 31, 127]),
    # a table the pages-a-step do not divide
    "pages_5": dict(b=2, hq=4, hk=2, d=64, ps=32, mp=5, lens=[159, 40]),
    "pages_7": dict(b=2, hq=4, hk=2, d=64, ps=32, mp=7, lens=[223, 128]),
    "pages_11": dict(b=2, hq=4, hk=2, d=64, ps=16, mp=11, lens=[175, 129]),
    "pages_13_int8": dict(b=2, hq=4, hk=2, d=64, ps=32, mp=13,
                          lens=[415, 384], dtype="int8"),
    # more heads than one step's VMEM budget holds: a head-block axis
    "head_blocks": dict(b=2, hq=64, hk=64, d=128, ps=16, mp=9,
                        lens=[143, 3]),
}


@pytest.mark.parametrize("name", sorted(_GEOMETRIES))
def test_kernel_geometries_match_dense_oracle(name):
    geo = dict(_GEOMETRIES[name])
    dtype = geo.setdefault("dtype", "float32")
    args, scales, ref = _paged(**geo)
    out = np.asarray(paged_decode_attention(*args, **scales,
                                            interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _oracle(*ref), rtol=_TOL[dtype],
                               atol=_TOL[dtype])


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_kernel_blocks_under_jit_and_scan(dtype):
    # two blocks of pages a slot, as the engine's tick runs it
    args, scales, ref = _paged(b=2, hq=8, hk=4, d=64, ps=32, mp=6,
                               lens=[130, 31], dtype=dtype)

    @jax.jit
    def run(*a):
        def step(carry, _):
            return carry, paged_decode_attention(*a, **scales,
                                                 interpret=True)
        return jax.lax.scan(step, 0, jnp.arange(2))[1]

    outs = np.asarray(run(*args))
    want = _oracle(*ref)
    for t in range(2):
        np.testing.assert_allclose(outs[t], want, rtol=_TOL[dtype],
                                   atol=_TOL[dtype])


def test_negative_position_attends_nothing():
    args, scales, _ = _paged(b=2, hq=4, hk=2, d=64, ps=16, mp=4,
                             lens=[20, 3])
    args[-1] = jnp.asarray([20, -1], jnp.int32)
    out = np.asarray(paged_decode_attention(*args, interpret=True))
    assert np.isfinite(out).all() and not out[1].any() and out[0].any()


_PLANS = {
    # (hq, hk, d, page, pages a slot, pool dtype, slots)
    "cell": (32, 32, 64, 16, 48, "bfloat16", 16),
    "mistral": (32, 8, 128, 16, 64, "bfloat16", 16),
    "int8": (32, 8, 128, 32, 40, "int8", 16),
    "cell_int8": (32, 32, 64, 32, 24, "int8", 16),
    "wide_f32": (64, 64, 128, 16, 64, "float32", 8),
}


@pytest.mark.parametrize("name", sorted(_PLANS))
def test_decode_plan_from_shapes(name):
    hq, hk, d, ps, mp, dt, slots = _PLANS[name]
    plan = decode_plan(hq, hk, d, ps, mp, dt, slots=slots)
    assert 0 < plan.vmem_bytes <= _VMEM_BUDGET
    assert hk % plan.heads == 0 and 1 <= plan.pages <= mp
    assert plan.grid == (slots, hk // plan.heads, -(-mp // plan.pages))
    assert plan.grid_steps == slots * (hk // plan.heads) \
        * -(-mp // plan.pages)
    # a step is a lane tile of keys where the pages allow it
    assert plan.pages * ps == 128 or plan.heads < hk
    # a page is moved as whole tiles: 128 lanes, the dtype's sublanes
    sub = {"float32": 8, "bfloat16": 16, "int8": 32}[dt]
    assert plan.fold * d % 128 == 0
    assert plan.pack * (ps // plan.fold) % sub == 0
    assert plan.heads % plan.pack == 0
    if name == "cell":
        # 24,576 steps of one 16 x 64 tile before (slots x heads x pages)
        assert plan.heads == 32 and plan.grid_steps <= 768
        assert plan.grid_steps == 96
        # two tokens a 128-lane row, two heads a bf16 sublane tile
        assert (plan.fold, plan.pack) == (2, 2)
    if name == "mistral":
        assert (plan.heads, plan.fold, plan.pack) == (8, 1, 1)
    if name == "wide_f32":
        assert plan.heads < hk          # the budget splits the heads


def test_decode_plan_reads_no_knob(monkeypatch):
    """Block sizes come from the shapes: the same plan whatever the
    environment says, and no argument selects one."""
    import inspect
    want = decode_plan(32, 32, 64, 16, 48, "bfloat16", slots=16)
    for var in ("PADDLE_TPU_AUTOTUNE", "PADDLE_TPU_DECODE_PAGES",
                "PADDLE_TPU_DECODE_HEADS"):
        monkeypatch.setenv(var, "1")
    assert decode_plan(32, 32, 64, 16, 48, "bfloat16", slots=16) == want
    assert set(inspect.signature(paged_decode_attention).parameters) == {
        "q", "k_pool", "v_pool", "block_tables", "lens", "k_scale",
        "v_scale", "sm_scale", "interpret",
        "select",       # an operand (a per-key mask), not a block size
        "kv_heads"}     # of pools stored as rows, which do not say it


def test_shape_contract_names_what_cannot_be_tiled():
    """On the chip a page moves as whole (sublane, 128) tiles: a head
    width that neither divides nor is a multiple of 128, and kv heads
    that cannot pack a sublane tile between them, are named."""
    with pytest.raises(ValueError, match=r"multiple of 128"):
        check_decode_shapes(8, 8, 96, 16, interpret=False,
                            kv_dtype="bfloat16")
    # d 64 at page 16 in bf16: 8 rows a head, so heads pack in pairs
    assert not decode_shape_problems(8, 8, 64, 16, interpret=False,
                                     kv_dtype="bfloat16")
    with pytest.raises(ValueError, match=r"hk=3 heads of 8 rows"):
        check_decode_shapes(3, 3, 64, 16, interpret=False,
                            kv_dtype="bfloat16")
    # interpret mode has no tiles
    assert not decode_shape_problems(3, 3, 96, 16, interpret=True,
                                     kv_dtype="bfloat16")


# -- the write: paged_kv_write against XLA's scatter, bit for bit -----------

def _write_case(b, s, hk, d, ps, mp, dtype, starts, n_valid, seed=0):
    """Pools (by heads and by rows), a call's tokens and their flat
    coordinates, and what XLA's scatter leaves in the pools."""
    from paddle_tpu.inference.paged import PagedState, _token_coords
    rng = np.random.default_rng(seed)
    n = b * mp + 1

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)

    pools = draw(n, hk, ps, d), draw(n, hk, ps, d)
    toks = draw(b, s, hk, d), draw(b, s, hk, d)
    bt = rng.permutation(np.arange(1, n, dtype=np.int32)).reshape(b, mp)
    state = PagedState(jnp.asarray(bt), jnp.asarray(starts, jnp.int32),
                       jnp.asarray(n_valid, jnp.int32))
    phys, off = _token_coords(state, s, ps, n)
    want = [p.at[phys, :, off, :].set(
        t.reshape(b * s, hk, d).astype(dtype), mode="drop")
        for p, t in zip(pools, toks)]
    shape = pool_rows_shape(n, hk, d, ps, dtype)
    return [p.reshape(shape) for p in pools], toks, (phys, off), want


def _same_bits(got, want, hk, d):
    got = pages_by_head(got, hk, d)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


_WRITES = {
    # the dense serve cell's heads and pages: two tokens a 128-lane row,
    # two heads a bf16 sublane tile -> pools of (n, 16, 16, 128)
    "cell_one_token": dict(b=4, s=1, hk=32, d=64, ps=16, mp=3,
                           dtype="bfloat16", starts=[0, 17, 31, 47],
                           n_valid=[1, 1, 1, 1], rows=(16, 16, 128)),
    "cell_dead_slots": dict(b=4, s=1, hk=32, d=64, ps=16, mp=3,
                            dtype="bfloat16", starts=[5, 16, 0, 40],
                            n_valid=[1, 0, 0, 1], rows=(16, 16, 128)),
    "cell_prompt": dict(b=1, s=40, hk=32, d=64, ps=16, mp=3,
                        dtype="bfloat16", starts=[0], n_valid=[37],
                        rows=(16, 16, 128)),
    # a start inside a page (a chunk after a chunk, a verify row)
    "cell_from_inside_a_page": dict(b=3, s=40, hk=32, d=64, ps=16, mp=5,
                                    dtype="bfloat16", starts=[21, 16, 7],
                                    n_valid=[40, 33, 1],
                                    rows=(16, 16, 128)),
    "cell_verify_rows": dict(b=4, s=5, hk=32, d=64, ps=16, mp=3,
                             dtype="bfloat16", starts=[14, 3, 27, 43],
                             n_valid=[5, 5, 0, 5], rows=(16, 16, 128)),
    # the doc-QA cell's: a head is a whole tile, rows are the page
    "docqa_one_token": dict(b=3, s=1, hk=4, d=128, ps=16, mp=4,
                            dtype="bfloat16", starts=[63, 0, 33],
                            n_valid=[1, 1, 1], rows=(4, 16, 128)),
    "docqa_chunk": dict(b=1, s=48, hk=4, d=128, ps=16, mp=5,
                        dtype="bfloat16", starts=[16], n_valid=[45],
                        rows=(4, 16, 128)),
    "f32_pool": dict(b=2, s=20, hk=8, d=64, ps=16, mp=3, dtype="float32",
                     starts=[3, 0], n_valid=[20, 11], rows=(8, 8, 128)),
    # more pages than one step takes: the windows alternate
    "many_steps": dict(b=6, s=70, hk=32, d=64, ps=16, mp=6,
                       dtype="bfloat16", starts=[9, 0, 16, 1, 15, 2],
                       n_valid=[70, 70, 3, 64, 17, 0],
                       rows=(16, 16, 128)),
    "nothing_valid": dict(b=3, s=7, hk=2, d=8, ps=4, mp=3,
                          dtype="float32", starts=[0, 5, 11],
                          n_valid=[0, 0, 0], rows=(1, 8, 8)),
}


@pytest.mark.parametrize("name", sorted(_WRITES))
def test_kv_write_equals_the_scatter_bit_for_bit(name):
    geo = dict(_WRITES[name])
    rows = geo.pop("rows")
    hk, d = geo["hk"], geo["d"]
    pools, toks, coords, want = _write_case(**geo)
    assert pools[0].shape[1:] == rows
    before = [np.asarray(p.astype(jnp.float32)) for p in pools]
    got = paged_kv_write(*pools, *toks, *coords, interpret=True)
    for g, w in zip(got, want):
        _same_bits(g, w, hk, d)
    if name == "nothing_valid":
        # nothing written, and no page past the pool read
        for g, was in zip(got, before):
            np.testing.assert_array_equal(
                np.asarray(g.astype(jnp.float32)), was)


@pytest.mark.parametrize("s", [1, 21])
def test_kv_write_under_jit_and_scan_with_the_pools_carried(s):
    """As the engine's tick runs it: the pools are the scan's carry and
    each step writes the tokens after the ones before."""
    from paddle_tpu.inference.paged import PagedState, _token_coords
    b, hk, d, ps, mp, steps = 3, 32, 64, 16, 6, 3
    pools, _, _, _ = _write_case(b, s, hk, d, ps, mp, "bfloat16",
                                 [0] * b, [s] * b)
    n = pools[0].shape[0]
    rng = np.random.default_rng(1)
    bt = jnp.asarray(rng.permutation(
        np.arange(1, n, dtype=np.int32)).reshape(b, mp))
    toks = jnp.asarray(rng.normal(size=(steps, 2, b, s, hk, d)),
                       jnp.bfloat16)
    lens0 = jnp.asarray([5, 16, 30], jnp.int32)
    n_valid = jnp.asarray([s, 0, max(s - 2, 1)], jnp.int32)

    def coords(lens):
        return _token_coords(PagedState(bt, lens, n_valid), s, ps, n)

    @jax.jit
    def run(kp, vp):
        def step(carry, kv):
            kp, vp, lens = carry
            kp, vp = paged_kv_write(kp, vp, kv[0], kv[1], *coords(lens),
                                    interpret=True)
            return (kp, vp, lens + n_valid), None
        return jax.lax.scan(step, (kp, vp, lens0), toks)[0][:2]

    got = run(*pools)
    want = [pages_by_head(p, hk, d) for p in pools]
    lens = lens0
    for t in range(steps):
        phys, off = coords(lens)
        want = [w.at[phys, :, off, :].set(
            toks[t, i].reshape(b * s, hk, d), mode="drop")
            for i, w in enumerate(want)]
        lens = lens + n_valid
    for g, w in zip(got, want):
        _same_bits(g, w, hk, d)


def test_kv_write_refuses_pools_not_stored_as_rows():
    pools, toks, coords, _ = _write_case(2, 1, 32, 64, 16, 2, "bfloat16",
                                         [0, 3], [1, 1])
    by_head = [pages_by_head(p, 32, 64) for p in pools]
    with pytest.raises(ValueError, match="not stored as rows"):
        paged_kv_write(*by_head, *toks, *coords, interpret=True)


@pytest.mark.parametrize("name", ["cell", "docqa", "f32_small"])
def test_decode_reads_pools_stored_as_rows(name):
    """The decode kernel over pools in the shape they are stored in
    (`kv_heads` said) gives what it gives over the same pools by heads."""
    hq, hk, d, ps, dtype = {"cell": (8, 8, 64, 16, "bfloat16"),
                            "docqa": (8, 2, 128, 16, "bfloat16"),
                            "f32_small": (4, 2, 8, 4, "float32")}[name]
    args, _, _ = _paged(b=2, hq=hq, hk=hk, d=d, ps=ps, mp=4,
                        lens=[3 * ps + 1, ps - 1], dtype=dtype)
    q, kp, vp, bt, lens = args
    want = paged_decode_attention(q, kp, vp, bt, lens, interpret=True)
    shape = pool_rows_shape(kp.shape[0], hk, d, ps, kp.dtype)
    if name == "cell":
        assert shape[1:] == (4, 16, 128)
    got = paged_decode_attention(q, kp.reshape(shape), vp.reshape(shape),
                                 bt, lens, interpret=True, kv_heads=hk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
