"""Pallas kernels vs pure-jnp oracles (ref.py) across shape/dtype sweeps
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.sgmv import sgmv
from repro.kernels.gqa_decode import block_len, gqa_decode
from repro.kernels.paged_decode import paged_gqa_decode
from repro.kernels.token_logprob import token_logprob_flat
from repro.rl.grpo import token_logprobs_chunked

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("R,d,r,dout,T", [
    (32, 64, 8, 48, 3), (100, 256, 16, 512, 5), (17, 48, 4, 40, 2),
    (64, 128, 32, 256, 8), (8, 72, 8, 72, 1), (256, 64, 8, 64, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sgmv_sweep(R, d, r, dout, T, dtype):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (R, d), dtype)
    a = (jax.random.normal(ks[1], (T, d, r), jnp.float32) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (T, r, dout), jnp.float32) * 0.1).astype(dtype)
    ids = jax.random.randint(ks[3], (R,), 0, T)
    y = sgmv(x, a, b, ids)
    want = ref.sgmv_ref(x, a, b, ids)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_sgmv_empty_group():
    """Tasks with zero rows must not corrupt neighbours."""
    x = jax.random.normal(KEY, (24, 32), jnp.float32)
    a = jax.random.normal(KEY, (4, 32, 4), jnp.float32) * 0.1
    b = jax.random.normal(KEY, (4, 4, 16), jnp.float32) * 0.1
    ids = jnp.array([0] * 12 + [3] * 12)          # groups 1, 2 empty
    np.testing.assert_allclose(np.asarray(sgmv(x, a, b, ids)),
                               np.asarray(ref.sgmv_ref(x, a, b, ids)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,H,KVH,hd,S", [
    (2, 4, 2, 16, 64), (3, 8, 2, 32, 128), (2, 4, 4, 16, 64),
    (1, 12, 2, 16, 96), (2, 16, 8, 64, 256), (2, 16, 8, 128, 96),
])
@pytest.mark.parametrize("softcap,window", [(0.0, 0), (50.0, 0), (0.0, 24)])
def test_gqa_decode_sweep(B, H, KVH, hd, S, softcap, window):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (B, S, KVH, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (B, S, KVH, hd), jnp.float32)
    pos = jax.random.randint(ks[3], (B,), 1, S)
    out = gqa_decode(q, ck, cv, pos, bs=32, softcap=softcap, window=window)
    want = ref.gqa_decode_ref(q, ck, cv, pos, softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_gqa_decode_bf16_cache():
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 4, 16), jnp.bfloat16)
    ck = jax.random.normal(ks[1], (2, 64, 2, 16), jnp.bfloat16)
    cv = jax.random.normal(ks[2], (2, 64, 2, 16), jnp.bfloat16)
    pos = jnp.array([13, 64])
    out = gqa_decode(q, ck, cv, pos, bs=32)
    want = ref.gqa_decode_ref(q, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def _paged_cache(key, B, n_pg, page, KVH, hd, dtype):
    """Random page pool + per-row block tables: rows own disjoint physical
    pages in shuffled order (plus the scratch page at index P)."""
    ks = jax.random.split(key, 3)
    P = B * n_pg + 3                      # a few never-owned pages too
    kp = jax.random.normal(ks[0], (P + 1, page, KVH, hd), dtype)
    vp = jax.random.normal(ks[1], (P + 1, page, KVH, hd), dtype)
    perm = np.asarray(jax.random.permutation(ks[2], P))[:B * n_pg]
    tbl = jnp.asarray(perm.reshape(B, n_pg).astype(np.int32))
    return kp, vp, tbl


@pytest.mark.parametrize("B,H,KVH,hd,n_pg,page", [
    (2, 4, 2, 16, 4, 16), (3, 8, 2, 32, 2, 64), (2, 4, 4, 16, 8, 8),
    (1, 12, 2, 16, 3, 32), (2, 16, 8, 64, 2, 128), (2, 16, 8, 128, 3, 16),
])
@pytest.mark.parametrize("softcap,window", [(0.0, 0), (50.0, 0), (0.0, 24)])
def test_paged_gqa_decode_sweep(B, H, KVH, hd, n_pg, page, softcap, window):
    """Paged flash-decode (block table via scalar prefetch) vs the
    gather-then-dense oracle across the gqa_decode sweep shapes."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp, vp, tbl = _paged_cache(ks[1], B, n_pg, page, KVH, hd, jnp.float32)
    pos = jax.random.randint(ks[2], (B,), 1, n_pg * page)
    out = paged_gqa_decode(q, kp, vp, tbl, pos, softcap=softcap,
                           window=window)
    want = ref.paged_gqa_decode_ref(q, kp, vp, tbl, pos, softcap=softcap,
                                    window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _ragged_pool(key, pos, n_pg, page, KVH, hd, dtype, poison=False):
    """Each row owns ceil(pos/page) shuffled pages; its table entries past
    that point at pages it owns too (never read). With ``poison`` every
    position at or past a row's pos, and every page only those entries
    name, is NaN, and so is the scratch page."""
    pos = np.asarray(pos)
    B = len(pos)
    live = -(-pos // page)
    n_live, n_dead = int(live.sum()), int((n_pg - live).sum())
    P = n_live + n_dead
    ks = jax.random.split(key, 3)
    kp = np.array(jax.random.normal(ks[0], (P + 1, page, KVH, hd),
                                    jnp.float32))
    vp = np.array(jax.random.normal(ks[1], (P + 1, page, KVH, hd),
                                    jnp.float32))
    perm = np.asarray(jax.random.permutation(ks[2], P))
    tbl = np.zeros((B, n_pg), np.int32)
    o_live, o_dead = 0, n_live
    for b in range(B):
        tbl[b, :live[b]] = perm[o_live:o_live + live[b]]
        tbl[b, live[b]:] = perm[o_dead:o_dead + n_pg - live[b]]
        o_live += live[b]
        o_dead += n_pg - live[b]
    if poison:
        for b in range(B):
            for s in range(n_pg):
                lo = max(0, int(pos[b]) - s * page)
                kp[tbl[b, s], lo:] = np.nan
                vp[tbl[b, s], lo:] = np.nan
        kp[P], vp[P] = np.nan, np.nan
    return (jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
            jnp.asarray(tbl))


# (pos per row, table width, page, window): pos of 1, on a page boundary,
# on a block boundary (ppb·page = 128 tokens at pages of 16), one past it,
# at the full table, rows of very different lengths, windows that start
# inside a block, table widths that are not a multiple of ppb
PAGED_EDGES = {
    "pos_one": ([1, 1, 1], 8, 16, 0),
    "page_boundary": ([16, 32, 48], 8, 16, 0),
    "block_boundary": ([128, 129, 127], 16, 16, 0),
    "full_table": ([128, 128, 127], 8, 16, 0),
    "ragged": ([1, 500, 37, 256, 17], 32, 16, 0),
    "window_mid_block": ([300, 171, 40], 20, 16, 200),
    "window_tiny": ([299, 130, 3], 20, 16, 5),
    "width_not_multiple": ([170, 100, 176], 11, 16, 0),
    "width_not_multiple_page8": ([150, 61, 200], 25, 8, 0),
    "one_page_blocks": ([300, 129, 5], 3, 128, 0),
}


@pytest.mark.parametrize("case", sorted(PAGED_EDGES))
@pytest.mark.parametrize("poison", [False, True])
@pytest.mark.parametrize("hd", [64, 128])
def test_paged_gqa_decode_edges(case, poison, hd):
    """Against the gather-then-dense oracle at the edges of the live-page
    walk, for Granite's head size (64, lane-padded) and Qwen3's (128). With ``poison`` every position past a row's pos, the pages its
    table names past its live count, and the scratch page hold NaN: none
    of them may reach the output (the oracle reads the clean pool)."""
    pos, n_pg, page, window = PAGED_EDGES[case]
    B, H, KVH = len(pos), 4, 2
    ks = jax.random.split(KEY, 2)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp, vp, tbl = _ragged_pool(ks[1], pos, n_pg, page, KVH, hd,
                               jnp.float32)
    want = ref.paged_gqa_decode_ref(q, kp, vp, tbl, jnp.asarray(pos),
                                    window=window)
    if poison:
        kp, vp, tbl = _ragged_pool(ks[1], pos, n_pg, page, KVH, hd,
                                   jnp.float32, poison=True)
    out = paged_gqa_decode(q, kp, vp, tbl, jnp.asarray(pos), window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "granite-3-2b"])
def test_paged_gqa_decode_model_heads_bf16(arch):
    """The benchmark's head shapes (Qwen3 16/8 × 128, Granite 32/8 × 64) in
    bf16, rows of ragged lengths over pages of 16."""
    from repro.configs import REGISTRY
    cfg = REGISTRY[arch]
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = [1, 70, 16, 129, 300, 512]
    ks = jax.random.split(KEY, 2)
    q = jax.random.normal(ks[0], (len(pos), H, hd), jnp.bfloat16)
    kp, vp, tbl = _ragged_pool(ks[1], pos, 32, 16, KVH, hd, jnp.bfloat16)
    out = paged_gqa_decode(q, kp, vp, tbl, jnp.asarray(pos))
    want = ref.paged_gqa_decode_ref(q, kp, vp, tbl, jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_paged_gqa_decode_bf16_cache():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 4, 16), jnp.bfloat16)
    kp, vp, tbl = _paged_cache(ks[1], 2, 4, 16, 2, 16, jnp.bfloat16)
    pos = jnp.array([13, 64])
    out = paged_gqa_decode(q, kp, vp, tbl, pos)
    want = ref.paged_gqa_decode_ref(q, kp, vp, tbl, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_paged_gqa_decode_matches_contiguous():
    """A paged cache whose block table is the identity must reproduce the
    contiguous gqa_decode kernel exactly (same tiles, different routing)."""
    ks = jax.random.split(KEY, 4)
    B, H, KVH, hd, n_pg, page = 2, 8, 2, 32, 4, 32
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (B, n_pg * page, KVH, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (B, n_pg * page, KVH, hd), jnp.float32)
    pos = jax.random.randint(ks[3], (B,), 1, n_pg * page)
    # lay the contiguous caches out as pages: row b owns pages b*n_pg..
    kp = jnp.concatenate([ck.reshape(B * n_pg, page, KVH, hd),
                          jnp.zeros((1, page, KVH, hd), jnp.float32)])
    vp = jnp.concatenate([cv.reshape(B * n_pg, page, KVH, hd),
                          jnp.zeros((1, page, KVH, hd), jnp.float32)])
    tbl = jnp.arange(B * n_pg, dtype=jnp.int32).reshape(B, n_pg)
    out = paged_gqa_decode(q, kp, vp, tbl, pos)
    want = gqa_decode(q, ck, cv, pos, bs=page)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_gqa_decode_aliased_block_tables():
    """COW prefix sharing (ISSUE 8) makes block tables alias the SAME
    physical pages across rows: every row of a GRPO group points its
    prompt-prefix entries at one shared page set and only the tail pages
    are private. The kernel indexes the pool through the per-row table, so
    aliased rows must read identically to rows with private copies of the
    same values — and rows at different positions within the shared pages
    must each mask correctly."""
    ks = jax.random.split(KEY, 4)
    B, H, KVH, hd, n_pg, page = 4, 8, 2, 32, 4, 16
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    # pool: 2 shared prefix pages + B private tail-region pages (+ scratch)
    P = 2 + 2 * B
    kp = jax.random.normal(ks[1], (P + 1, page, KVH, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (P + 1, page, KVH, hd), jnp.float32)
    tbl = np.zeros((B, n_pg), np.int32)
    tbl[:, :2] = [0, 1]                       # all rows share pages 0,1
    for b in range(B):
        tbl[b, 2:] = [2 + 2 * b, 3 + 2 * b]   # private tails
    # rows at different depths, including one still inside the shared pages
    pos = jnp.array([page + 3, 2 * page, 3 * page + 5, 4 * page - 1])
    out = paged_gqa_decode(q, kp, vp, jnp.asarray(tbl), pos)
    want = ref.paged_gqa_decode_ref(q, kp, vp, jnp.asarray(tbl), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # aliasing is value-transparent: materialize private copies of the
    # shared pages per row and the outputs must match bit-for-bit
    kp2, vp2 = np.asarray(kp), np.asarray(vp)
    kp2 = np.concatenate([kp2, kp2[[0, 1]].repeat(B, 0).reshape(
        2 * B, page, KVH, hd)])
    vp2 = np.concatenate([vp2, vp2[[0, 1]].repeat(B, 0).reshape(
        2 * B, page, KVH, hd)])
    tbl2 = tbl.copy()
    for b in range(B):
        tbl2[b, :2] = [P + 1 + b, P + 1 + B + b]
    out2 = paged_gqa_decode(q, jnp.asarray(kp2), jnp.asarray(vp2),
                            jnp.asarray(tbl2), pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


@pytest.mark.parametrize("R,d,V", [(16, 32, 64), (50, 48, 100), (8, 24, 52),
                                   (128, 64, 512)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_token_logprob_sweep(R, d, V, softcap):
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (R, d), jnp.float32)
    w = jax.random.normal(ks[1], (d, V), jnp.float32) * 0.3
    t = jax.random.randint(ks[2], (R,), 0, V)
    lp, ent = token_logprob_flat(h, w, t, bm=8, bv=32, bk=16, softcap=softcap)
    want_lp, want_ent = ref.token_logprob_ref(h, w, t, softcap)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(want_ent),
                               rtol=1e-4, atol=1e-4)


def test_ops_wrappers_shapes():
    """ops.py public API: [B, S, ...] wrappers."""
    B, S, d, V = 2, 8, 16, 40
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (B, S, d), jnp.float32)
    w = jax.random.normal(ks[1], (d, V), jnp.float32)
    t = jax.random.randint(ks[2], (B, S), 0, V)
    lp, ent = ops.token_logprob(h, w, t)
    assert lp.shape == (B, S) and ent.shape == (B, S)
    want_lp, _ = ref.token_logprob_ref(h, w, t)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,bs,want", [(96, 512, 96), (1024, 512, 512),
                                       (600, 512, 200), (36, 512, 36),
                                       (13, 8, 1)])
def test_gqa_decode_block_len(S, bs, want):
    """Every cache length gets a dividing KV block (8-aligned where one
    exists), so use_kernel never needs the einsum for a static window."""
    assert block_len(S, bs) == want


def test_gqa_decode_untiled_cache_length():
    """A cache longer than the default block that the block does not
    divide runs through the kernel (block 200 for S=600)."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 8, 32), jnp.float32)
    ck = jax.random.normal(ks[1], (2, 600, 4, 32), jnp.float32)
    cv = jax.random.normal(ks[2], (2, 600, 4, 32), jnp.float32)
    pos = jnp.array([311, 600])
    np.testing.assert_allclose(np.asarray(gqa_decode(q, ck, cv, pos)),
                               np.asarray(ref.gqa_decode_ref(q, ck, cv, pos)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("V", [49155, 151936])
def test_token_logprob_padded_vocab(V):
    """Real vocabularies at the default tiles: 49155 is padded to 128
    lanes, and neither divides the 1024-wide tile, so the last tile is
    partial; the masked columns must not reach lse or entropy."""
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (16, 64), jnp.float32)
    w = jax.random.normal(ks[1], (64, V), jnp.float32) * 0.3
    t = jax.random.randint(ks[2], (16,), V - 200, V)    # tail-tile targets
    lp, ent = token_logprob_flat(h, w, t)
    want_lp, want_ent = ref.token_logprob_ref(h, w, t)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(want_ent),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_token_logprob_grad_matches_chunked(softcap):
    """The kernel's custom VJP gives the gradients of the chunked jnp
    path (what use_kernel=True trains with)."""
    ks = jax.random.split(KEY, 4)
    B, S, d, V = 2, 12, 32, 300
    h = jax.random.normal(ks[0], (B, S, d), jnp.float32)
    w = jax.random.normal(ks[1], (d, V), jnp.float32) * 0.3
    t = jax.random.randint(ks[2], (B, S), 0, V)
    wt = jax.random.normal(ks[3], (B, S), jnp.float32)

    def loss(fn):
        def f(h, w):
            lp, ent = fn(h, w, t, softcap)
            return jnp.sum(lp * wt) + 0.1 * jnp.sum(ent)
        return f

    got = jax.grad(loss(ops.token_logprob), argnums=(0, 1))(h, w)
    want = jax.grad(loss(token_logprobs_chunked), argnums=(0, 1))(h, w)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   rtol=1e-5, atol=1e-6)
