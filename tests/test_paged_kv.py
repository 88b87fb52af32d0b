"""Paged int8 KV cache: allocator invariants, kernel parity, scheduler.

Four layers of coverage, mirroring how the feature is built:

  * :class:`repro.core.paged_kv.BlockAllocator` invariants (no double free,
    no leaks after retirement, all-or-nothing exhaustion);
  * the block-table Pallas decode kernel (interpret mode) and the XLA
    gather fallback against the dense ref oracle;
  * per-slot prefill writes *only* its own blocks, and the paged decode
    path bit-matches the dense-cache decode path on identical history;
  * the ``launch/serve.py`` scheduler admits via per-slot prefill only —
    no batch-wide prefill ever happens (demand-paged admission; the
    over-commit / preemption / fault machinery has its own suites in
    ``test_overcommit.py`` and ``test_faults.py``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import attention as core_attn
from repro.core import paged_kv
from repro.core import split_softmax as ss
from repro.core.lut import LUTConfig
from repro.kernels import ops
from repro.launch import steps as st
from repro.models import transformer as T

CFG = LUTConfig(scale_z=2.6 / 127)
EXP_LUT, RECIP_LUT = ss.make_luts(CFG)
SCALES = (jnp.float32(0.01), jnp.float32(0.012), jnp.float32(0.02))


# ------------------------------ allocator -----------------------------------

def test_allocator_alloc_free_recycle():
    a = paged_kv.BlockAllocator(8)          # ids 1..7 allocatable
    first = a.alloc(3)
    assert len(set(first)) == 3
    assert paged_kv.TRASH_BLOCK not in first
    assert a.live_count == 3 and a.free_count == 4
    a.free(first)
    assert a.live_count == 0 and a.free_count == 7
    # FIFO recycling: freed ids come back after the untouched ones
    again = a.alloc(7)
    assert sorted(again) == list(range(1, 8))


def test_allocator_rejects_double_free_and_foreign_ids():
    a = paged_kv.BlockAllocator(8)
    ids = a.alloc(2)
    a.free(ids)
    with pytest.raises(paged_kv.BlockAllocationError):
        a.free(ids)                         # double free
    with pytest.raises(paged_kv.BlockAllocationError):
        a.free([paged_kv.TRASH_BLOCK])      # reserved id
    with pytest.raises(paged_kv.BlockAllocationError):
        a.free([5])                         # never handed out


def test_allocator_exhaustion_is_all_or_nothing():
    a = paged_kv.BlockAllocator(4)          # 3 allocatable
    a.alloc(2)
    with pytest.raises(paged_kv.BlockAllocationError):
        a.alloc(2)                          # only 1 free
    assert a.free_count == 1                # failed alloc took nothing


def test_gather_kv_addressing(rng):
    # position p of slot s lives at pages[table[s, p//bk], :, p%bk, :]
    nb, h, bk, d = 6, 2, 4, 8
    pages = jnp.asarray(rng.integers(-128, 128, (nb, h, bk, d)), jnp.int8)
    table = jnp.asarray([[3, 1], [5, 2]], jnp.int32)
    out = paged_kv.gather_kv(pages, table)
    assert out.shape == (2, h, 2 * bk, d)
    for s in range(2):
        for p in range(2 * bk):
            want = pages[int(table[s, p // bk]), :, p % bk, :]
            np.testing.assert_array_equal(np.asarray(out[s, :, p, :]),
                                          np.asarray(want))


# ------------------------- kernel: table gather -----------------------------

PAGED_GRID = [
    # b, hq, hkv, mb (blocks/slot), d, bk, lens (None: drawn at random)
    (2, 4, 2, 2, 64, 128, None),
    (1, 8, 1, 4, 128, 64, None),
    (3, 6, 6, 3, 64, 128, None),
    # 32-token pages, so a grid step takes 16 table entries (512 positions).
    # Group 1, Hkv 8: length 1, a page and a chunk boundary and one past it,
    # the full table; 37 entries are not a whole number of chunks.
    (5, 8, 8, 37, 128, 32, (1, 32, 512, 513, 37 * 32)),
    # group 4, Hkv 2, D 64: the short slot's last chunk is dead
    (3, 8, 2, 20, 64, 32, (511, 640, 64)),
    # group 8, Hkv 1: one chunk, a slot that ends inside its first page
    (2, 8, 1, 16, 128, 32, (512, 31)),
]


@pytest.mark.parametrize("shape", PAGED_GRID)
@pytest.mark.parametrize("window", [None, 64])
def test_paged_decode_matches_ref(rng, shape, window):
    """The block-table kernel against the oracle, and bitwise against the
    dense kernel on the gathered cache at ``block_k`` = the page: both add
    page by page in table order.  The XLA twin sums every position in one
    product, so it is bitwise only while each sum is an exact f32 integer:
    with a 64-token window here.  Where the lengths are given, table rows
    end in the trash block past each slot's length, as the engine leaves
    them, and the trash block holds garbage."""
    b, hq, hkv, mb, d, bk, given = shape
    num_blocks = 1 + b * mb
    q1 = rng.integers(-128, 128, (b, hq, d)).astype(np.int8)
    qf = jnp.asarray(rng.normal(0, 0.5, (b, hq, d)), jnp.float32)
    k_pages = jnp.asarray(
        rng.integers(-128, 128, (num_blocks, hkv, bk, d)), jnp.int8)
    v_pages = jnp.asarray(
        rng.integers(-128, 128, (num_blocks, hkv, bk, d)), jnp.int8)
    # non-trivial table: slots own a shuffled set of non-trash blocks
    perm = rng.permutation(np.arange(1, num_blocks)).reshape(b, mb)
    if given is None:
        lens = rng.integers(1, mb * bk + 1, (b,))
    else:
        lens = np.asarray(given)
        perm[np.arange(mb)[None, :] >= -(-lens[:, None] // bk)] = (
            paged_kv.TRASH_BLOCK)
    table = jnp.asarray(perm, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    args = (q1, k_pages, v_pages, table, *SCALES, lens, EXP_LUT, RECIP_LUT)
    ref = ops.splitmax_decode_paged(*args, cfg=CFG, impl="ref",
                                    window=window)
    ker = ops.splitmax_decode_paged(*args, cfg=CFG, impl="interpret",
                                    window=window)
    xla = ops.splitmax_decode_paged(*args, cfg=CFG, impl="xla",
                                    window=window)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    k_dense = paged_kv.gather_kv(k_pages, table)
    v_dense = paged_kv.gather_kv(v_pages, table)
    for name, paged_op, dense_op, q in (
            ("composed", ops.splitmax_decode_paged, ops.splitmax_decode, q1),
            ("fused", ops.splitmax_decode_fused_paged,
             ops.splitmax_decode_fused, qf)):
        rest = (*SCALES, lens, EXP_LUT, RECIP_LUT)
        paged = paged_op(q, k_pages, v_pages, table, *rest, cfg=CFG,
                         window=window, impl="interpret")
        dense = dense_op(q, k_dense, v_dense, *rest, cfg=CFG, window=window,
                         block_k=bk, impl="interpret")
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense),
                                      err_msg=name)
        if window is not None:
            twin = paged_op(q, k_pages, v_pages, table, *rest, cfg=CFG,
                            window=window, impl="xla")
            np.testing.assert_array_equal(np.asarray(paged),
                                          np.asarray(twin), err_msg=name)


@pytest.mark.parametrize("window", [None, 64])
def test_fetch_table_copies_each_live_page_once(rng, window):
    """The paged decode kernel's page inputs copy a pool block only when
    their block changes from one grid step to the next.  Every live table
    entry must name its own block, and after the first step an input copies
    only a block that is live for it in the current slot: each live page is
    fetched at most once, and a dead entry never costs a copy."""
    from repro.kernels.splitmax_decode import _fetch_table, _live_pages
    bk, pages, mb = 32, 16, 37
    n_chunks = -(-mb // pages)
    lens = np.asarray([0, 1, 100, 512, 513, 40, 3, mb * bk])
    table = rng.integers(1, 1000, (len(lens), mb))
    fetch = np.asarray(_fetch_table(jnp.asarray(table, jnp.int32),
                                    jnp.asarray(lens, jnp.int32), window, bk,
                                    pages, n_chunks))
    assert fetch.shape == (len(lens), n_chunks * pages)
    held, copies, live_total = None, 0, 0
    for s, length in enumerate(lens):
        lo, hi = (int(x) for x in _live_pages(int(length), window, bk, mb))
        live = range(lo, hi)
        live_total += len(live)
        for c in range(n_chunks):
            now = fetch[s, c * pages:(c + 1) * pages]
            for i in range(pages):
                j = c * pages + i
                if j in live:
                    assert now[i] == table[s, j], (s, j)
                if held is not None and now[i] != held[i]:
                    copies += 1
                    assert now[i] in {table[s, x] for x in live
                                      if x % pages == i}, (s, c, i)
            held = now
    assert copies <= live_total


def test_paged_ref_equals_dense_ref_on_gathered_cache(rng):
    """The paged ref path *is* gather + dense decode — sanity-pin that."""
    b, hq, hkv, mb, d, bk = 2, 4, 2, 2, 64, 128
    num_blocks = 1 + b * mb
    q1 = rng.integers(-128, 128, (b, hq, d)).astype(np.int8)
    k_pages = jnp.asarray(
        rng.integers(-128, 128, (num_blocks, hkv, bk, d)), jnp.int8)
    v_pages = jnp.asarray(
        rng.integers(-128, 128, (num_blocks, hkv, bk, d)), jnp.int8)
    table = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks)).reshape(b, mb), jnp.int32)
    lens = jnp.asarray([100, 256], jnp.int32)
    paged = ops.splitmax_decode_paged(
        q1, k_pages, v_pages, table, *SCALES, lens, EXP_LUT, RECIP_LUT,
        cfg=CFG, impl="ref")
    dense = ops.splitmax_decode(
        q1, paged_kv.gather_kv(k_pages, table),
        paged_kv.gather_kv(v_pages, table), *SCALES, lens, EXP_LUT,
        RECIP_LUT, cfg=CFG, impl="ref")
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


def test_trash_block_tail_at_block_boundary(rng):
    """A retired-adjacent edge: a slot whose table row *ends on the trash
    block* (block 0) with cache_len landing exactly on a block boundary, so
    the valid region touches the last owned block's final row and the trash
    block contributes nothing.  The output must be invariant to whatever
    garbage the trash block holds — checked on the gather_kv/XLA fallback
    and the interpret kernel, for the composed and fused paged paths."""
    b, hq, hkv, mb, d, bk = 2, 4, 2, 3, 64, 32
    num_blocks = 1 + b * (mb - 1)
    q1 = rng.integers(-128, 128, (b, hq, d)).astype(np.int8)
    qf = jnp.asarray(rng.normal(0, 0.5, (b, hq, d)), jnp.float32)
    kp = rng.integers(-128, 128, (num_blocks, hkv, bk, d)).astype(np.int8)
    vp = rng.integers(-128, 128, (num_blocks, hkv, bk, d)).astype(np.int8)
    # slots own 2 real blocks each; the third table entry is the trash block
    table = jnp.asarray([[1, 2, paged_kv.TRASH_BLOCK],
                         [3, 4, paged_kv.TRASH_BLOCK]], jnp.int32)
    lens = jnp.asarray([2 * bk, 2 * bk], jnp.int32)  # exact block boundary

    def run(trash_fill):
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[paged_kv.TRASH_BLOCK] = trash_fill
        vp2[paged_kv.TRASH_BLOCK] = trash_fill
        kj, vj = jnp.asarray(kp2), jnp.asarray(vp2)
        outs = {}
        for impl in ("xla", "interpret"):
            outs[f"composed.{impl}"] = ops.splitmax_decode_paged(
                q1, kj, vj, table, *SCALES, lens, EXP_LUT, RECIP_LUT,
                cfg=CFG, impl=impl)
            outs[f"fused.{impl}"] = ops.splitmax_decode_fused_paged(
                qf, kj, vj, table, *SCALES, lens, EXP_LUT, RECIP_LUT,
                cfg=CFG, impl=impl)
        return outs

    a = run(np.int8(0))
    bb = run(np.full((hkv, bk, d), 127, np.int8))   # worst-case garbage
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]),
                                      np.asarray(bb[key]),
                                      err_msg=f"trash block leaked: {key}")
    # and the gather itself must see exactly the two owned blocks
    dense = ops.splitmax_decode(
        q1, paged_kv.gather_kv(jnp.asarray(kp), table),
        paged_kv.gather_kv(jnp.asarray(vp), table), *SCALES, lens,
        EXP_LUT, RECIP_LUT, cfg=CFG, block_k=bk, impl="xla")
    np.testing.assert_array_equal(np.asarray(a["composed.xla"]),
                                  np.asarray(dense))


# ------------------------ model: prefill + decode ---------------------------

def _smoke_cfg():
    return get_arch("tinyllama_1p1b").smoke.replace(dtype="float32")


def test_per_slot_prefill_touches_only_own_blocks(rng):
    cfg = _smoke_cfg()
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(0))
    block_k, max_len, slots = 8, 16, 2
    bps = paged_kv.blocks_per_seq(max_len, block_k)      # 2
    cache = T.make_paged_cache(cfg, slots, max_len, block_k=block_k,
                               num_blocks=1 + 3 * bps)   # headroom: 6 blocks
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, 8)), jnp.int32)
    _, cache = T.prefill_paged(params, tok, cfg, cache,
                               jnp.arange(slots, dtype=jnp.int32),
                               jnp.asarray([[1, 2], [3, 4]], jnp.int32),
                               calibrate=True)
    before_k = np.asarray(cache["kv"]["k_pages"])
    before_tbl = np.asarray(cache["kv"]["block_table"])
    # admit into slot 1 with fresh blocks; slot 0 must be untouched
    tok1 = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    _, cache = T.prefill_paged(params, tok1, cfg, cache,
                               jnp.asarray([1], jnp.int32),
                               jnp.asarray([[5, 6]], jnp.int32),
                               calibrate=False)
    after_k = np.asarray(cache["kv"]["k_pages"])
    np.testing.assert_array_equal(after_k[:, [1, 2]], before_k[:, [1, 2]])
    np.testing.assert_array_equal(
        np.asarray(cache["kv"]["block_table"])[0], before_tbl[0])
    # and the new slot's blocks did change (the prompt is non-degenerate)
    assert not np.array_equal(after_k[:, [5, 6]], before_k[:, [5, 6]])


def test_paged_decode_bit_matches_dense(rng):
    """Same params, same prompt: dense cache and paged cache produce
    bit-identical logits through prefill + 8 greedy decode steps.  The paged
    XLA path gathers through the table and then runs the *same* grouped
    decode as the dense path, so this is exact equality, not allclose."""
    cfg = _smoke_cfg()
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(1))
    block_k, max_len = 8, 32                  # mb*block_k == max_len exactly
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)), jnp.int32)

    dense = T.make_cache(cfg, 1, max_len)
    last_d, dense = T.prefill(params, tok, cfg, dense)

    bps = paged_kv.blocks_per_seq(max_len, block_k)
    paged = T.make_paged_cache(cfg, 1, max_len, block_k=block_k)
    last_p, paged = T.prefill_paged(
        params, tok, cfg, paged, jnp.asarray([0], jnp.int32),
        jnp.arange(1, 1 + bps, dtype=jnp.int32)[None, :], calibrate=True)

    np.testing.assert_array_equal(np.asarray(last_d), np.asarray(last_p))
    np.testing.assert_array_equal(
        np.asarray(dense["kv"]["scale_k"]),
        np.asarray(paged["kv"]["scale_k"]))

    tok_d = jnp.argmax(last_d, -1).astype(jnp.int32)
    tok_p = jnp.argmax(last_p, -1).astype(jnp.int32)
    for _ in range(8):
        log_d, dense = T.decode_step(params, tok_d, cfg, dense)
        log_p, paged = T.decode_step(params, tok_p, cfg, paged)
        np.testing.assert_array_equal(np.asarray(log_d), np.asarray(log_p))
        tok_d = jnp.argmax(log_d, -1).astype(jnp.int32)
        tok_p = jnp.argmax(log_p, -1).astype(jnp.int32)


# ----------------------- kernel: in-place K/V append ------------------------

APPEND_GRID = [
    # b, hkv, bk, d
    (8, 4, 32, 64),
    (6, 2, 8, 128),
    (7, 16, 32, 128),           # OLMo-1B's K/V widths, an odd slot count
]


@pytest.mark.parametrize("shape", APPEND_GRID)
def test_paged_kv_append_matches_scatter(rng, shape):
    """The aliased append kernel (interpret mode) writes every live slot's
    row where the XLA scatter does and leaves every other row as it was:
    offsets 0 and block_k - 1 among random ones, retired slots on the trash
    block, every non-trash page compared."""
    b, hkv, bk, d = shape
    num_blocks = 1 + 3 * b
    k_pages = jnp.asarray(rng.integers(-128, 128, (num_blocks, hkv, bk, d)),
                          jnp.int8)
    v_pages = jnp.asarray(rng.integers(-128, 128, (num_blocks, hkv, bk, d)),
                          jnp.int8)
    blk = rng.permutation(np.arange(1, num_blocks))[:b]
    blk[1::3] = paged_kv.TRASH_BLOCK                 # retired slots
    off = rng.integers(0, bk, (b,))
    off[0], off[2] = 0, bk - 1
    k_new = jnp.asarray(rng.integers(-128, 128, (b, hkv, d)), jnp.int8)
    v_new = jnp.asarray(rng.integers(-128, 128, (b, hkv, d)), jnp.int8)
    args = (k_pages, v_pages, jnp.asarray(blk, jnp.int32),
            jnp.asarray(off, jnp.int32), k_new, v_new)
    got = ops.paged_kv_append(*args, impl="interpret")
    want = ops.paged_kv_append(*args, impl="xla")
    for g, w, name in zip(got, want, ("k", "v")):
        np.testing.assert_array_equal(np.asarray(g)[1:], np.asarray(w)[1:],
                                      err_msg=name)


def _scatter_append(k_pages, v_pages, blk, off, k_new, v_new, spec):
    return (k_pages.at[blk, :, off, :].set(k_new),
            v_pages.at[blk, :, off, :].set(v_new))


def _decode_step_sliced(params, token, cfg, cache):
    """The paged decode step as it was before the pool rode in the layer
    scan's carry: each layer's pages are sliced out, scanned as inputs,
    returned as outputs and set back, and each token's K/V row goes in by
    an XLA scatter (patched in while this function is traced)."""
    x = T.embed_tokens(params, token[:, None], cfg)
    kvc = cache["kv"]
    offset = 0
    for seg_params, (kind, n) in zip(params["segments"], T._layer_kinds(cfg)):
        sl = slice(offset, offset + n)

        def body(x, xs):
            layer_params, kp, vp, s_k, s_v = xs
            slice_ = {"kv": dict(kvc, k_pages=kp, v_pages=vp, scale_k=s_k,
                                 scale_v=s_v)}
            x, new = T._block_decode_paged(layer_params, x, slice_, cfg, kind)
            return x, (new["kv"]["k_pages"], new["kv"]["v_pages"])

        x, (kp, vp) = T.maybe_scan(
            body, x, (seg_params, kvc["k_pages"][sl], kvc["v_pages"][sl],
                      kvc["scale_k"][sl], kvc["scale_v"][sl]), cfg)
        kvc = dict(kvc, k_pages=kvc["k_pages"].at[sl].set(kp),
                   v_pages=kvc["v_pages"].at[sl].set(vp))
        offset += n
    kvc = dict(kvc, length=kvc["length"] + 1)
    cache = dict(cache, kv=kvc, length=cache["length"] + 1)
    return T.unembed(params, x, cfg)[:, 0], cache


@pytest.mark.parametrize("impl,scan", [("xla", True), ("interpret", True),
                                       ("xla", False)])
def test_decode_step_in_place_matches_sliced_layers(rng, impl, scan,
                                                    monkeypatch):
    """Several steps of the paged decode step, which carries the whole pool
    through the layer scan, hands each layer the flattened pool with an
    offset block table and appends each row with the aliased kernel,
    against the sliced-per-layer formulation with the scatter append:
    logits and both pools bitwise equal, a retired slot and a page boundary
    included."""
    cfg = _smoke_cfg().replace(attn_impl=impl, scan_layers=scan)
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(3))
    block_k, max_len, slots = 8, 32, 3
    bps = paged_kv.blocks_per_seq(max_len, block_k)
    cache = T.make_paged_cache(cfg, slots, max_len, block_k=block_k)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    # slots 0 and 2 hold requests; slot 1 is retired on the trash block
    _, cache = T.prefill_paged(
        params, tok, cfg, cache, jnp.asarray([0, 2], jnp.int32),
        jnp.asarray([np.arange(1, 1 + bps), np.arange(1 + 2 * bps,
                                                      1 + 3 * bps)],
                    jnp.int32), calibrate=True)
    ref = jax.tree.map(lambda a: a, cache)
    step = jax.jit(lambda p, t, c: T.decode_step(p, t, cfg, c))

    def sliced_fn(p, t, c):
        with monkeypatch.context() as m:
            m.setattr(core_attn, "paged_append", _scatter_append)
            return _decode_step_sliced(p, t, cfg, c)

    sliced = jax.jit(sliced_fn)
    token = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots,)), jnp.int32)
    for _ in range(4):                    # positions 6..9 cross a page
        logits, cache = step(params, token, cache)
        want, ref = sliced(params, token, ref)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        for name in ("k_pages", "v_pages", "length"):
            np.testing.assert_array_equal(np.asarray(cache["kv"][name]),
                                          np.asarray(ref["kv"][name]),
                                          err_msg=name)
        token = jnp.argmax(logits, -1).astype(jnp.int32)


# --------------------------- scheduler: serve -------------------------------

def test_serve_admission_is_per_slot_only(rng):
    """requests > slots: every admission is a per-slot prefill — the
    demand-paged scheduler never batch-prefills — and no blocks leak."""
    from repro.launch import serve as srv
    cfg = _smoke_cfg()
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(2))
    prompts = [rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
               for _ in range(5)]
    stats = srv.serve(params, cfg, prompts, slots=2, gen=4,
                      cache_kind="paged", block_k=8)
    assert stats["batch_prefills"] == 0
    assert stats["slot_prefills"] == 5      # one per request, none batched
    assert stats["leaked_blocks"] == 0
    assert sorted(stats["finished"]) == [0, 1, 2, 3, 4]
    assert all(len(v) == 4 for v in stats["finished"].values())


# ---------------- speculative: append / rollback / truncate -----------------

def test_append_kv_addressing(rng):
    """T new tokens land at base_len[b]+t through the table; everything
    else — earlier positions, other slots' blocks, the trash block — is
    byte-identical before and after."""
    nb, h, bk, d, t = 7, 2, 8, 4, 3
    pages = jnp.asarray(rng.integers(-128, 128, (nb, h, bk, d)), jnp.int8)
    table = jnp.asarray([[2, 5, 1], [6, 3, 4]], jnp.int32)
    base = jnp.asarray([5, 14], jnp.int32)        # non-block-aligned starts
    vals = jnp.asarray(rng.integers(-128, 128, (2, t, h, d)), jnp.int8)
    out = np.asarray(paged_kv.append_kv(pages, table, base, vals))

    touched = set()
    for s in range(2):
        for i in range(t):
            p = int(base[s]) + i
            blk, off = int(table[s, p // bk]), p % bk
            np.testing.assert_array_equal(out[blk, :, off, :],
                                          np.asarray(vals[s, i]))
            touched.add((blk, off))
    before = np.asarray(pages)
    for blk in range(nb):
        for off in range(bk):
            if (blk, off) not in touched:
                np.testing.assert_array_equal(out[blk, :, off, :],
                                              before[blk, :, off, :])


def test_append_kv_clamps_overrun_to_last_cell(rng):
    """A slot appending past its table capacity (retired-but-stepping, or a
    gamma overshoot) must clamp into the final addressed cell instead of
    indexing out of bounds; the last token wins that cell."""
    nb, h, bk, d, mb, t = 4, 1, 4, 2, 2, 3
    pages = jnp.zeros((nb, h, bk, d), jnp.int8)
    table = jnp.asarray([[1, 2]], jnp.int32)
    base = jnp.asarray([mb * bk - 1], jnp.int32)  # one cell of room left
    vals = jnp.asarray(rng.integers(1, 128, (1, t, h, d)), jnp.int8)
    out = np.array(paged_kv.append_kv(pages, table, base, vals))
    np.testing.assert_array_equal(out[2, :, bk - 1, :],
                                  np.asarray(vals[0, -1]))
    out[2, :, bk - 1, :] = 0
    assert not out.any()                          # nothing else was written


def test_rollback_slot_trashes_tail_and_preserves_others():
    bk, mb, slots = 8, 4, 2
    pool = paged_kv.init_kv_pages(1, 10, 1, bk, 4, slots, mb)
    pool = dict(pool,
                block_table=jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]],
                                        jnp.int32),
                length=jnp.asarray([29, 31], jnp.int32))
    rolled = paged_kv.rollback_slot(pool, jnp.int32(0), jnp.int32(12))
    # 12 tokens span ceil(12/8)=2 blocks: [1, 2] kept, [3, 4] trashed
    np.testing.assert_array_equal(np.asarray(rolled["block_table"][0]),
                                  [1, 2, paged_kv.TRASH_BLOCK,
                                   paged_kv.TRASH_BLOCK])
    np.testing.assert_array_equal(np.asarray(rolled["block_table"][1]),
                                  [5, 6, 7, 8])   # other slot untouched
    np.testing.assert_array_equal(np.asarray(rolled["length"]), [12, 31])


def test_rollback_slot_block_boundary():
    """new_len landing exactly on a block boundary keeps exactly
    new_len/block_k blocks — the ceil must not round an exact fit up."""
    bk, mb = 8, 3
    pool = paged_kv.init_kv_pages(1, 8, 1, bk, 4, 1, mb)
    pool = dict(pool, block_table=jnp.asarray([[1, 2, 3]], jnp.int32),
                length=jnp.asarray([20], jnp.int32))
    rolled = paged_kv.rollback_slot(pool, jnp.int32(0), jnp.int32(2 * bk))
    np.testing.assert_array_equal(np.asarray(rolled["block_table"][0]),
                                  [1, 2, paged_kv.TRASH_BLOCK])
    # and rolling back to zero trashes the whole row
    empty = paged_kv.rollback_slot(pool, jnp.int32(0), jnp.int32(0))
    assert not np.asarray(empty["block_table"][0]).any()


def test_tail_blocks_matches_rollback_and_never_frees_trash():
    bk = 8
    assert paged_kv.tail_blocks([1, 2, 3, 4], 12, bk) == [3, 4]
    assert paged_kv.tail_blocks([1, 2, 3], 2 * bk, bk) == [3]
    assert paged_kv.tail_blocks([1, 2, 3], 0, bk) == [1, 2, 3]
    # a row that already ends on the trash block must not "free" it
    assert paged_kv.tail_blocks([1, 2, paged_kv.TRASH_BLOCK], 8, bk) == [2]


def test_rollback_freed_blocks_recycle_through_allocator():
    """End-to-end host bookkeeping: rollback's tail goes back to the
    allocator and is handed out again, with no leak and no double free."""
    bk = 8
    a = paged_kv.BlockAllocator(5)                # ids 1..4
    ids = a.alloc(4)
    tail = paged_kv.tail_blocks(ids, 9, bk)       # keep ceil(9/8)=2
    assert tail == ids[2:]
    a.free(tail)
    assert a.live_count == 2 and a.free_count == 2
    assert a.alloc(2) == tail                     # FIFO re-entry
    a.free(tail)
    with pytest.raises(paged_kv.BlockAllocationError):
        a.free(tail)                              # double free still caught


def test_truncate_lengths_is_length_only(rng):
    pool = paged_kv.init_kv_pages(2, 6, 1, 4, 4, 3, 2)
    pool = dict(pool, length=jnp.asarray([7, 8, 3], jnp.int32))
    cut = paged_kv.truncate_lengths(pool, jnp.asarray([5, 8, 0]))
    np.testing.assert_array_equal(np.asarray(cut["length"]), [5, 8, 0])
    assert cut["length"].dtype == jnp.int32
    for key in ("k_pages", "v_pages", "block_table", "scale_k", "scale_v"):
        assert cut[key] is pool[key]              # untouched, not copied
