"""Paged KV-cache conformance (DESIGN.md §12).

Three tiers, least to most integrated:

  * :class:`PageAllocator` invariants — no double allocation, alloc/free
    round-trips restore the free list, page-major row maps.  Deterministic
    versions always run; hypothesis widens them to random op sequences when
    it is installed (CI), mirroring test_qlearning_props.py.
  * page-table gather == dense-cache layout: for random interleaved
    allocation orders (with slot retirement and page reuse),
    ``gather_pages`` must reproduce the exact dense ``(B, L, ...)`` view the
    non-paged engine carries, zeros in unmapped rows.
  * teacher-forced decode oracles on the paged model path — qwen3 (pure
    pool) and gemma2 sliding-window (dense ring layers × pool global layers,
    the riskiest interaction): greedy tokens through ``decode_step`` with a
    deliberately interleaved page layout must equal batch-1 dense decode
    bit-for-bit.  Plus a scatter-isolation regression: a parked lane
    (row_map −1) must not touch the pool — negative indices WRAP under
    scatter mode="drop", which silently corrupted the last pool row.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.paging import PageAllocator
from repro.models import family_module, layers as L, reduced

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                # pragma: no cover - CI has it
    HAVE_HYPOTHESIS = False

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# PageAllocator invariants (deterministic tier — always runs)
# ---------------------------------------------------------------------------

def _run_ops(alloc: PageAllocator, rng, n_ops: int):
    """Random alloc/free interleaving; returns the live allocations and
    checks the no-double-allocation invariant after every op."""
    live: list[list[int]] = []
    seen: set[int] = set()
    for _ in range(n_ops):
        if live and (rng.random() < 0.4 or alloc.n_free == 0):
            pages = live.pop(int(rng.integers(len(live))))
            alloc.free(pages)
            seen.difference_update(pages)
        elif alloc.n_free:
            pages = alloc.alloc(int(rng.integers(1, alloc.n_free + 1)))
            assert not seen & set(pages), "page handed out twice"
            assert len(set(pages)) == len(pages)
            seen.update(pages)
            live.append(pages)
        assert alloc.n_free + len(seen) == alloc.n_pages
    return live


def test_allocator_never_double_allocates():
    rng = np.random.default_rng(0)
    for seed in range(8):
        _run_ops(PageAllocator(11, 3), np.random.default_rng(seed), 60)


def test_alloc_free_round_trip_restores_free_list():
    alloc = PageAllocator(9, 4)
    initial = alloc.free_pages
    rng = np.random.default_rng(7)
    live = _run_ops(alloc, rng, 40)
    for pages in live:
        alloc.free(pages)
    assert alloc.free_pages == initial


def test_allocator_rejects_bad_ops():
    alloc = PageAllocator(4, 2)
    with pytest.raises(MemoryError, match="exceeds"):
        alloc.alloc(5)
    pages = alloc.alloc(2)
    with pytest.raises(ValueError, match="not currently allocated"):
        alloc.free([3])
    alloc.free(pages)
    with pytest.raises(ValueError, match="not currently allocated"):
        alloc.free(pages)                          # double free
    with pytest.raises(ValueError):
        PageAllocator(0, 2)
    with pytest.raises(ValueError):
        alloc.alloc(-1)


def test_pages_for_and_row_layout():
    alloc = PageAllocator(8, 4)
    assert [alloc.pages_for(r) for r in (0, 1, 4, 5, 8)] == [0, 1, 1, 2, 2]
    pages = alloc.alloc(2)                          # [0, 1] (lowest first)
    assert pages == [0, 1]
    assert alloc.rows(pages, 6) == [0, 1, 2, 3, 4, 5]   # page-major
    alloc.free([pages[0]])
    other = alloc.alloc(1)
    assert other == [0]                             # lowest free reused
    with pytest.raises(ValueError, match="exceed"):
        alloc.rows([1], 5)


# ---------------------------------------------------------------------------
# page-table gather == dense layout (deterministic tier)
# ---------------------------------------------------------------------------

def _random_paged_layout(rng, n_pages=6, page_size=3, slots=3, max_seq=12):
    """Grow slots in random interleaved order, with random retirement and
    page reuse, mirroring engine bookkeeping.  Returns (pool, row_map,
    dense, used) where dense is the ground-truth per-slot layout and used
    counts each slot's written rows (rows beyond it are don't-care)."""
    alloc = PageAllocator(n_pages, page_size)
    rows_total = n_pages * page_size
    pool = np.zeros((rows_total, 2, 2), np.float32)
    dense = np.zeros((slots, max_seq, 2, 2), np.float32)
    row_map = np.full((slots, max_seq), -1, np.int32)
    pages: list[list[int]] = [[] for _ in range(slots)]
    used = np.zeros(slots, np.int32)
    stamp = 1.0
    for _ in range(60):
        s = int(rng.integers(slots))
        if rng.random() < 0.15 and pages[s]:       # retire: free + clear
            alloc.free(pages[s])
            pages[s] = []
            used[s] = 0
            row_map[s, :] = -1
            dense[s] = 0.0
            continue
        if used[s] >= max_seq:
            continue
        if len(pages[s]) * page_size <= used[s]:   # grow one page
            if not alloc.n_free:
                continue
            pages[s] += alloc.alloc(1)
            mapped = min(len(pages[s]) * page_size, max_seq)
            row_map[s, :mapped] = alloc.rows(pages[s], mapped)
        val = np.full((2, 2), stamp, np.float32)
        stamp += 1.0
        pool[row_map[s, used[s]]] = val
        dense[s, used[s]] = val
        used[s] += 1
    return pool, row_map, dense, used


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_gather_pages_matches_dense_layout(seed):
    pool, row_map, dense, used = _random_paged_layout(
        np.random.default_rng(seed))
    view = np.asarray(L.gather_pages(jnp.asarray(pool),
                                     jnp.asarray(row_map)))
    # rows >= used are don't-care: mapped-but-unwritten rows of a reused
    # page may hold a retired request's stale KV, and attention masks them
    # out by pos — the invariant is equality on every *written* row, plus
    # zero-fill wherever the page table is unmapped
    max_seq = row_map.shape[1]
    written = np.arange(max_seq)[None, :] < used[:, None]
    np.testing.assert_array_equal(view[written], dense[written])
    np.testing.assert_array_equal(view[row_map < 0], 0.0)


# ---------------------------------------------------------------------------
# hypothesis tier (runs where hypothesis is installed, e.g. CI)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @st.composite
    def allocator_runs(draw):
        n_pages = draw(st.integers(min_value=1, max_value=16))
        page_size = draw(st.integers(min_value=1, max_value=8))
        seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        n_ops = draw(st.integers(min_value=1, max_value=80))
        return n_pages, page_size, seed, n_ops

    @given(allocator_runs())
    @settings(max_examples=60, deadline=None)
    def test_allocator_invariants_property(run):
        n_pages, page_size, seed, n_ops = run
        alloc = PageAllocator(n_pages, page_size)
        initial = alloc.free_pages
        live = _run_ops(alloc, np.random.default_rng(seed), n_ops)
        for pages in live:
            alloc.free(pages)
        assert alloc.free_pages == initial

    @given(st.integers(min_value=0, max_value=2**31 - 1),
           st.integers(min_value=1, max_value=8),
           st.integers(min_value=1, max_value=4))
    @settings(max_examples=15, deadline=None)
    def test_gather_pages_matches_dense_property(seed, n_pages, page_size):
        pool, row_map, dense, used = _random_paged_layout(
            np.random.default_rng(seed), n_pages=n_pages,
            page_size=page_size, slots=2, max_seq=8)
        view = np.asarray(L.gather_pages(jnp.asarray(pool),
                                         jnp.asarray(row_map)))
        written = np.arange(row_map.shape[1])[None, :] < used[:, None]
        np.testing.assert_array_equal(view[written], dense[written])
        np.testing.assert_array_equal(view[row_map < 0], 0.0)


# ---------------------------------------------------------------------------
# teacher-forced decode oracle on the paged model path
# ---------------------------------------------------------------------------

def _family(arch, **over):
    cfg = reduced(get_config(arch), **over)
    return cfg, family_module(cfg), family_module(cfg).init(cfg, KEY, tp=1)


def _dense_teacher_forced(cfg, mod, params, prompt, max_new, max_seq):
    """Batch-1 dense decode, one token at a time — the §11 oracle."""
    cache = mod.init_cache(cfg, 1, max_seq, 1)
    logits = None
    for t, tok in enumerate(prompt):
        logits, cache = mod.decode_step(
            params, cfg, cache, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([t], jnp.int32), tp=1, impl="xla")
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    while len(toks) < max_new and pos < max_seq:
        logits, cache = mod.decode_step(
            params, cfg, cache, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([pos], jnp.int32), tp=1, impl="xla")
        toks.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    return toks


def _paged_teacher_forced(cfg, mod, params, prompts, max_new, max_seq,
                          page_size):
    """Lockstep greedy decode of several prompts through the PAGED path:
    one shared pool, pages allocated on demand — slots growing in lockstep
    produce an interleaved (non-contiguous) physical layout, so any
    confusion between logical and physical rows shows up as a token flip."""
    slots = len(prompts)
    n_pages = -(-max_seq // page_size) * slots
    alloc = PageAllocator(n_pages, page_size)
    cache = mod.init_paged_cache(cfg, slots, n_pages * page_size, max_seq, 1)
    row_map = np.full((slots, max_seq), -1, np.int32)
    pages: list[list[int]] = [[] for _ in range(slots)]
    pos = np.zeros(slots, np.int64)
    outs: list[list[int]] = [[] for _ in range(slots)]
    has_pool = "pool" in jax.tree_util.tree_leaves(mod.paged_slot_axes(cfg))

    def live(s):
        return len(outs[s]) < max_new and pos[s] < max_seq

    while any(live(s) for s in range(slots)):
        toks = np.zeros((slots, 1), np.int32)
        step_pos = np.full(slots, max_seq, np.int64)
        for s, prompt in enumerate(prompts):
            if not live(s):
                continue
            if has_pool and len(pages[s]) * page_size < pos[s] + 1:
                pages[s] += alloc.alloc(1)
                mapped = min(len(pages[s]) * page_size, max_seq)
                row_map[s, :mapped] = alloc.rows(pages[s], mapped)
            toks[s, 0] = prompt[pos[s]] if pos[s] < len(prompt) \
                else outs[s][-1]
            step_pos[s] = pos[s]
        logits, cache = mod.decode_step(
            params, cfg, cache, jnp.asarray(toks),
            jnp.asarray(step_pos, jnp.int32), tp=1, impl="xla",
            row_map=jnp.asarray(row_map))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for s, prompt in enumerate(prompts):
            if step_pos[s] == max_seq:
                continue
            pos[s] += 1
            if pos[s] >= len(prompt):               # prompt consumed: emit
                outs[s].append(int(nxt[s]))
    return outs


PAGED_ORACLE_CASES = [
    ("qwen3-8b", ()),
    # sliding window smaller than the prompts: dense per-slot rings on the
    # local layers share the step with paged pools on the global layers
    ("gemma2-2b", (("local_window", 5), ("n_layers", 4))),
]


@pytest.mark.parametrize("arch,over", PAGED_ORACLE_CASES,
                         ids=[c[0] for c in PAGED_ORACLE_CASES])
@pytest.mark.parametrize("page_size", [2, 5])
def test_paged_decode_matches_teacher_forced_oracle(arch, over, page_size):
    cfg, mod, params = _family(arch, **dict(over))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (7, 3, 9)]
    max_new, max_seq = 5, 24
    oracle = [_dense_teacher_forced(cfg, mod, params, p, max_new, max_seq)
              for p in prompts]
    outs = _paged_teacher_forced(cfg, mod, params, prompts, max_new,
                                 max_seq, page_size)
    for s, (got, want) in enumerate(zip(outs, oracle)):
        assert got == want, f"{arch} ps={page_size}: slot {s} diverged"


def test_parked_lane_cannot_touch_the_pool():
    """Scatter isolation: a lane with an all-−1 page table and a parked
    position must leave the pool bit-identical.  Regression for the
    mode=\"drop\" negative-index WRAP, which routed parked-lane writes onto
    the last pool row and corrupted whichever request owned it."""
    cfg, mod, params = _family("qwen3-8b")
    max_seq, rows = 16, 16
    cache = mod.init_paged_cache(cfg, 2, rows, max_seq, 1)
    row_map = np.full((2, max_seq), -1, np.int32)
    row_map[0, :4] = [2, 3, 0, 1]                  # slot 0 maps 2 pages
    before = jax.tree_util.tree_map(np.asarray, cache)
    toks = jnp.asarray([[5], [9]], jnp.int32)
    pos = jnp.asarray([1, max_seq], jnp.int32)     # slot 1 parked
    _, cache = mod.decode_step(params, cfg, cache, toks, pos, tp=1,
                               impl="xla", row_map=jnp.asarray(row_map))

    def changed_rows(b, a):
        moved = np.asarray(b != np.asarray(a))
        return set(np.nonzero(moved.any(axis=tuple(range(1, moved.ndim)))
                              if moved.ndim > 1 else moved)[0].tolist())

    for name in ("k", "v"):
        for layer in range(before["all"][name].shape[0]):
            touched = changed_rows(before["all"][name][layer],
                                   cache["all"][name][layer])
            assert touched <= {3}, \
                f"layer {layer} {name}: parked lane wrote rows {touched}"


# ---------------------------------------------------------------------------
# the in-place decode == the per-layer form it replaced
# ---------------------------------------------------------------------------

def _per_layer_attention(params, cfg, x, *, positions, tp, impl, window=0,
                         cache=None, layer=None, cache_pos=None,
                         row_map=None):
    """``layers.attention`` in its per-layer form: the layer's cache sliced
    out of the stack with rows (KV, hd), the new rows scattered into the
    slice (``pool.at[prow]``, ``cache.at[b, rows]``), the layer's rows read
    from the slice with ``gather_pages``, and the slice written back."""
    pd = cfg.padded(tp)
    h, kv, hd = pd.n_heads, pd.n_kv_heads, cfg.head_dim
    q = L._split_heads(x @ params["wq"], h, hd)
    k = L._split_heads(x @ params["wk"], kv, hd)
    v = L._split_heads(x @ params["wv"], kv, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, params["q_norm"])
        k = L.rms_norm(k, params["k_norm"])
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    paged = row_map is not None and cache["k"].ndim == 4
    lead = 1 if paged else 2                 # row axes of one layer's slice
    cache_len = row_map.shape[1] if paged else cache["k"].shape[2]
    ring = not paged and window > 0 and cache_len == window
    bsz, sq = q.shape[:2]
    cpos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (bsz,))
    new_cache, views = {}, {}

    def put(name, val):
        stacked = cache[name]
        sl = stacked[layer]
        sl = sl.reshape(sl.shape[:lead] + val.shape[2:])
        s = val.shape[1]
        if ring and s >= window:
            val = val[:, s - window:]
        rows = cpos[:, None] + (s - val.shape[1]) + jnp.arange(val.shape[1])
        val = val.astype(sl.dtype)
        if paged:
            prow = jnp.take_along_axis(
                row_map, jnp.clip(rows, 0, cache_len - 1), axis=1)
            prow = jnp.where((rows < cache_len) & (prow >= 0), prow,
                             sl.shape[0])
            sl = sl.at[prow].set(val, mode="drop")
            views[name] = L.gather_pages(sl, row_map)
        else:
            if ring:
                rows = rows % window
            sl = sl.at[jnp.arange(bsz)[:, None], rows].set(val, mode="drop")
            views[name] = sl
        new_cache[name] = stacked.at[layer].set(
            sl.reshape(stacked.shape[1:]))

    if "k_scale" in cache:
        def quant(z):
            sc = jnp.max(jnp.abs(z.astype(jnp.float32)), axis=-1,
                         keepdims=True) / 127.0 + 1e-12
            return jnp.round(z.astype(jnp.float32) / sc
                             ).astype(jnp.int8), sc[..., 0]
        (kq, ks), (vq, vs) = quant(k), quant(v)
        for name, val in (("k", kq), ("v", vq), ("k_scale", ks),
                          ("v_scale", vs)):
            put(name, val)
        ck = views["k"].astype(jnp.float32) * views["k_scale"][..., None]
        cv = views["v"].astype(jnp.float32) * views["v_scale"][..., None]
    else:
        put("k", k)
        put("v", v)
        ck, cv = views["k"], views["v"]
    last = cpos + sq - 1
    if ring:
        slots = jnp.arange(cache_len)
        kpos = last[:, None] - jax.lax.rem(
            (last[:, None] - slots) % window + window, jnp.int32(window))
        out = L._decode_attention(q, ck, cv, cfg, last, 0, kpos=kpos)
    else:
        out = L._decode_attention(q, ck, cv, cfg, last, window)
    return out.reshape(bsz, sq, h * hd) @ params["wo"], new_cache


def _filled(cache, rng):
    """Every leaf filled with values of its dtype, so that reads of rows the
    step does not write see something other than zeros."""
    def fill(x):
        if x.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, x.shape), jnp.int8)
        if x.dtype == jnp.float32:                   # int8 KV's scales
            return jnp.asarray(rng.uniform(1e-3, 2e-2, x.shape), x.dtype)
        return jnp.asarray(rng.standard_normal(x.shape), x.dtype)
    return jax.tree_util.tree_map(fill, cache)


# case -> (arch, config overrides, paged); "-unstaged" gathers straight
# from the stacked pool, as layer pools past ``L.STAGE_POOL_BYTES`` do
IN_PLACE_CASES = {
    "pool": ("qwen3-8b", {}, True),
    "pool-unstaged": ("qwen3-8b", {}, True),
    "dense": ("qwen3-8b", {}, False),
    "gemma2-local-global": ("gemma2-2b", {"local_window": 5, "n_layers": 4},
                            True),
    "int8-pool": ("qwen3-8b", {"kv_int8": True}, True),
    "int8-pool-unstaged": ("qwen3-8b", {"kv_int8": True}, True),
}


@pytest.mark.parametrize("case", list(IN_PLACE_CASES))
def test_in_place_decode_matches_per_layer_reference(case, monkeypatch):
    """The decode step that scatters into and gathers from the stacked
    cache in place gives the same logits and the same cache, bit for bit,
    as the per-layer form: a multi-token chunk, then single-token steps
    with the slots at different depths and one slot parked."""
    arch, over, paged = IN_PLACE_CASES[case]
    if case.endswith("-unstaged"):
        monkeypatch.setattr(L, "STAGE_POOL_BYTES", 0)
    cfg, mod, params = _family(arch, **over)
    slots, max_seq, page = 3, 12, 4
    rng = np.random.default_rng(11)
    row_map = None
    if paged:
        n_pages = slots * max_seq // page + 1            # one spare page
        cache = mod.init_paged_cache(cfg, slots, n_pages * page, max_seq, 1)
        perm = rng.permutation(n_pages)
        table = np.full((slots, max_seq), -1, np.int32)
        for s in range(slots):
            for j in range(max_seq // page - (s == 2)):  # slot 2: 2 pages
                table[s, j * page:(j + 1) * page] = \
                    perm[s * 3 + j] * page + np.arange(page)
        row_map = jnp.asarray(table)
    else:
        cache = mod.init_cache(cfg, slots, max_seq, 1)
    cache = _filled(cache, rng)
    steps = [(3, [0, 4, 2])] + [(1, [3 + t, 7 + t, 5 + t]) for t in range(3)]
    steps.append((1, [6, 10, max_seq]))                  # slot 2 parked
    for width, pos in steps:
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (slots, width)),
                           jnp.int32)
        args = (params, cfg, cache, toks, jnp.asarray(pos, jnp.int32))
        with monkeypatch.context() as m:
            m.setattr(L, "attention", _per_layer_attention)
            want_logits, want = mod.decode_step(*args, row_map=row_map)
        got_logits, got = mod.decode_step(*args, row_map=row_map)
        assert np.array_equal(np.asarray(got_logits),
                              np.asarray(want_logits)), (case, pos)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                (case, pos, jax.tree_util.keystr(path))
        cache = got
