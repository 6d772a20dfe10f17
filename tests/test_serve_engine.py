"""The continuous-batching serving engine (DESIGN.md §11).

Three layers:

  * FCFS scheduler unit tests — pure bookkeeping, no model: admission
    order, lowest-free-slot placement, slot reuse after retirement,
    concurrency caps, request validation.
  * per-request budget semantics on a live engine.
  * the staggered-admission parity gate: per-request outputs from the
    continuous-batched engine must be BIT-IDENTICAL to a sequential
    single-request reference (fresh slots=1 engine per request) for every
    admission pattern, on every decode-capable family — including a
    gemma2-style ring-buffer-window case whose prompts overflow the window.
    This is the invariant the old serving loop violated five different ways
    (shared scalar pos, zero-token prefill pollution, cross-request pos
    desync, clamped last row, stale-KV leaks).
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.paging import PriorityScheduler
from repro.launch.serve import FCFSScheduler, PagedServeEngine, Request, \
    ServeEngine, make_requests, serve_requests
from repro.models import family_module, reduced

KEY = jax.random.PRNGKey(0)


def _req(rid, n=3, max_new=4, **kw):
    return Request(rid, np.arange(1, n + 1, dtype=np.int32), max_new, **kw)


# ---------------------------------------------------------------------------
# Request validation (satellite: real next_token field, no empty prompts)
# ---------------------------------------------------------------------------

def test_request_rejects_empty_prompt():
    with pytest.raises(ValueError, match="non-empty"):
        Request(0, np.array([], np.int32), 4)
    with pytest.raises(ValueError, match="1-D"):
        Request(0, np.ones((2, 2), np.int32), 4)
    with pytest.raises(ValueError, match="max_new"):
        _req(0, max_new=0)


def test_request_next_token_is_a_real_field():
    r = _req(0)
    assert r.next_token == -1                  # not a getattr default
    assert "next_token" in {f.name for f in
                            __import__("dataclasses").fields(Request)}


# ---------------------------------------------------------------------------
# FCFS scheduler (model-free)
# ---------------------------------------------------------------------------

def test_fcfs_admission_order_and_lowest_slot_first():
    s = FCFSScheduler(3)
    for i in range(5):
        s.submit(_req(i))
    placed = s.admit()
    assert [(slot, r.rid) for slot, r in placed] == [(0, 0), (1, 1), (2, 2)]
    assert [r.rid for r in s.queue] == [3, 4]
    assert s.admit() == []                     # full: nothing placed


def test_fcfs_slot_reuse_after_retirement():
    s = FCFSScheduler(2)
    for i in range(4):
        s.submit(_req(i))
    s.admit()
    done = s.retire(0)
    assert done.rid == 0 and s.n_active == 1
    placed = s.admit()                         # rid 2 lands in freed slot 0
    assert [(slot, r.rid) for slot, r in placed] == [(0, 2)]
    s.retire(1)
    assert [(slot, r.rid) for slot, r in s.admit()] == [(1, 3)]
    s.retire(1)
    with pytest.raises(ValueError, match="not occupied"):
        s.retire(1)


def test_fcfs_concurrency_cap():
    s = FCFSScheduler(4, max_concurrency=1)
    for i in range(3):
        s.submit(_req(i))
    assert len(s.admit()) == 1                 # sequential baseline mode
    assert s.admit() == []
    s.retire(0)
    placed = s.admit()
    assert len(placed) == 1 and placed[0][1].rid == 1


def test_fcfs_has_work():
    s = FCFSScheduler(1)
    assert not s.has_work()
    s.submit(_req(0))
    assert s.has_work()
    s.admit()
    assert s.has_work()
    s.retire(0)
    assert not s.has_work()


# ---------------------------------------------------------------------------
# live-engine lifecycle
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _family(arch, **over):
    cfg = reduced(get_config(arch), **dict(over))
    mod = family_module(cfg)
    return cfg, mod.init(cfg, KEY, tp=1)


def test_per_request_budget_retires_early():
    """A request's own max_seq budget retires it even when max_new and the
    engine-wide max_seq would allow more: prompt P=3, budget B=6 -> one
    prefill token + (B-P) decode tokens."""
    cfg, params = _family("qwen3-8b")
    eng = ServeEngine(cfg, params, slots=2, max_seq=32)
    eng.submit(_req(0, n=3, max_new=50, max_seq=6))
    eng.submit(_req(1, n=3, max_new=4))
    done = eng.run()
    assert len(done[0].out) == 1 + (6 - 3)
    assert len(done[1].out) == 4
    # prompt must leave room under its budget: graceful rejection, not a
    # raise (DESIGN.md §14) — the request turns terminal immediately
    rej = _req(2, n=6, max_new=2, max_seq=6)
    assert eng.submit(rej) is False
    assert rej.status == "REJECTED"
    assert eng.run() == [rej]          # reported exactly once via run()


def test_max_new_one_finishes_at_prefill():
    cfg, params = _family("qwen3-8b")
    eng = ServeEngine(cfg, params, slots=1, max_seq=16)
    eng.submit(_req(0, max_new=1))
    done = eng.run()
    assert len(done[0].out) == 1 and eng.decode_steps == 0


# ---------------------------------------------------------------------------
# staggered-admission parity (the tentpole gate)
# ---------------------------------------------------------------------------

def _reference_outputs(cfg, params, requests, max_seq):
    """Sequential single-request reference: each request decoded alone in a
    fresh one-slot engine — nothing to be polluted by."""
    out = {}
    for r in requests:
        eng = ServeEngine(cfg, params, slots=1, max_seq=max_seq)
        eng.submit(Request(r.rid, r.prompt.copy(), r.max_new))
        out[r.rid] = eng.run()[0].out
    return out


def _teacher_forced_outputs(cfg, params, requests, max_seq):
    """Independent oracle sharing NOTHING with the engine's admission path:
    no one-shot prefill, no pack_slot_cache, no slot scatter — just the
    prompt fed one token at a time through decode_step at incremental
    positions.  A bug in the prefill/ring-fold machinery would cancel out
    between the engine and the single-slot reference above; it cannot
    cancel out here."""
    import jax.numpy as jnp

    from repro.models import family_module

    mod = family_module(cfg)
    out = {}
    for r in requests:
        cache = mod.init_cache(cfg, 1, max_seq, 1)
        for t, tok in enumerate(r.prompt):
            logits, cache = mod.decode_step(
                params, cfg, cache, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray([t], jnp.int32), tp=1, impl="xla")
        toks = [int(jnp.argmax(logits[0, -1]))]
        pos = len(r.prompt)
        while len(toks) < r.max_new and pos < max_seq:
            logits, cache = mod.decode_step(
                params, cfg, cache, jnp.asarray([[toks[-1]]], jnp.int32),
                jnp.asarray([pos], jnp.int32), tp=1, impl="xla")
            toks.append(int(jnp.argmax(logits[0, -1])))
            pos += 1
        out[r.rid] = toks
    return out


def _make_requests(cfg, n, max_new, seed):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab,
                                    size=int(rng.integers(2, 9)))
                    .astype(np.int32), max_new) for i in range(n)]


# admission patterns: {step index -> how many queued requests to submit}
PATTERN_BURST = {0: 6}                        # all up front, 6 reqs > 4 slots
PATTERN_TRICKLE = {0: 3, 2: 2, 5: 1}          # arrivals join mid-decode

FAMILY_CASES = [
    ("qwen3-8b", (), [PATTERN_BURST, PATTERN_TRICKLE]),
    ("rwkv6-3b", (), [PATTERN_BURST, PATTERN_TRICKLE]),
    ("zamba2-2.7b", (), [PATTERN_TRICKLE]),
    # ring-buffer sliding window smaller than most prompts: the repacked
    # ring must equal what sequential decode would have left in it
    ("gemma2-2b", (("local_window", 5), ("n_layers", 4)),
     [PATTERN_BURST, PATTERN_TRICKLE]),
]


@pytest.mark.parametrize("arch,over,patterns", FAMILY_CASES,
                         ids=[c[0] for c in FAMILY_CASES])
def test_staggered_parity_bit_identical(arch, over, patterns):
    cfg, params = _family(arch, **dict(over))
    max_seq, max_new = 32, 6
    base = _make_requests(cfg, 6, max_new, seed=1)
    ref = _reference_outputs(cfg, params, base, max_seq)
    for pattern in patterns:
        eng = ServeEngine(cfg, params, slots=4, max_seq=max_seq)
        pending = [Request(r.rid, r.prompt.copy(), r.max_new) for r in base]
        done, step = [], 0
        while pending or eng.scheduler.has_work():
            for _ in range(pattern.get(step, 0)):
                eng.submit(pending.pop(0))
            done.extend(eng.step())
            step += 1
        assert sorted(r.rid for r in done) == [r.rid for r in base]
        for r in done:
            assert r.out == ref[r.rid], \
                f"{arch}: request {r.rid} diverged under pattern {pattern}"


@pytest.mark.parametrize("arch,over", [("qwen3-8b", ()),
                                       ("gemma2-2b", (("local_window", 5),
                                                      ("n_layers", 4)))],
                         ids=["qwen3-8b", "gemma2-2b-ring"])
def test_one_shot_prefill_matches_teacher_forced_decode(arch, over):
    """The admission path (one-shot prefill + pack_slot_cache + slot
    scatter) against an oracle that never uses it: token-by-token
    teacher-forced decode.  Catches prefill/ring-fold bugs that would
    cancel out between the engine and the single-slot reference."""
    cfg, params = _family(arch, **dict(over))
    reqs = _make_requests(cfg, 4, 5, seed=2)
    oracle = _teacher_forced_outputs(cfg, params, reqs, max_seq=32)
    eng = ServeEngine(cfg, params, slots=4, max_seq=32)
    for r in reqs:
        eng.submit(r)
    for r in eng.run():
        assert r.out == oracle[r.rid], f"{arch}: request {r.rid} diverged"


def test_sequential_mode_matches_batched_outputs():
    """max_concurrency=1 (the benchmark baseline) must produce the same
    per-request outputs — batching changes wall-clock, never content."""
    cfg, params = _family("qwen3-8b")
    base = _make_requests(cfg, 5, 5, seed=3)
    copy = lambda: [Request(r.rid, r.prompt.copy(), r.max_new) for r in base]
    batched, stats_b = serve_requests(cfg, params, copy(), slots=4,
                                      max_seq=32)
    seq, stats_s = serve_requests(cfg, params, copy(), slots=4, max_seq=32,
                                  max_concurrency=1)
    assert [r.out for r in batched] == [r.out for r in seq]
    assert stats_b["generated"] == stats_s["generated"]
    assert stats_b["decode_steps"] < stats_s["decode_steps"]


# ---------------------------------------------------------------------------
# Request priority validation (satellite)
# ---------------------------------------------------------------------------

def test_request_priority_validation():
    with pytest.raises(ValueError, match="priority"):
        _req(0, priority=-1)
    with pytest.raises(ValueError, match="priority"):
        _req(0, priority=1.5)
    with pytest.raises(ValueError, match="priority"):
        _req(0, priority=True)
    assert _req(0, priority=np.int64(2)).priority == 2


def test_make_requests_heterogeneous_mix():
    cfg, _ = _family("qwen3-8b")
    reqs = make_requests(cfg, 11, 6, seed=0, long_every=11,
                         long_lengths=(24, 33), priorities=(0, 2),
                         max_new_spread=2)
    assert len(reqs[10].prompt) >= 24          # every 11th is long
    assert all(len(r.prompt) < 24 for r in reqs[:10])
    assert [r.priority for r in reqs[:4]] == [0, 2, 0, 2]
    assert {r.max_new for r in reqs} <= set(range(4, 9))
    assert len({r.max_new for r in reqs}) > 1  # actually heterogeneous


# ---------------------------------------------------------------------------
# PriorityScheduler conformance (model-free)
# ---------------------------------------------------------------------------

def test_preempt_requeue_preserves_fifo_within_class():
    s = PriorityScheduler(2, age_steps=0)
    reqs = [_req(i, priority=1) for i in range(4)]
    for r in reqs:
        s.submit(r)
    assert s.place(s.peek()) == 0              # rid 0
    assert s.place(s.peek()) == 1              # rid 1
    s.preempt(0)
    # the preempted request re-enters at its original submit position:
    # ahead of rids 2/3 that were submitted after it
    assert [r.rid for r in s.queues[1]] == [0, 2, 3]
    assert s.peek().rid == 0
    assert reqs[0].preemptions == 1


def test_priority_order_fifo_within_class():
    s = PriorityScheduler(1, age_steps=0)
    for rid, prio in [(0, 2), (1, 0), (2, 2), (3, 0)]:
        s.submit(_req(rid, priority=prio))
    order = []
    while s.n_waiting:
        r = s.peek()
        s.place(r)
        order.append(r.rid)
        s.retire(0)
    assert order == [1, 3, 0, 2]               # class order, FIFO inside


def test_aging_lets_low_priority_overtake():
    s = PriorityScheduler(1, age_steps=2)
    low = _req(100, priority=3)
    s.submit(low)
    for i in range(6):
        s.submit(_req(i, priority=0))
        s.tick()
    # waited 6 ticks -> effective 3 - 6//2 = 0; oldest submit wins the tie
    assert s.effective_priority(low) == 0
    assert s.peek().rid == 100


# ---------------------------------------------------------------------------
# paged engine: admission gates, preemption, no starvation
# ---------------------------------------------------------------------------

def test_paged_admission_blocked_at_zero_pages_resumes_on_retirement():
    """Admission is driven by free pages: a free slot alone is not enough.
    r0's growth drains the pool to zero free pages; r1 (same class, so no
    preemption) must wait until r0 retires, then run to the exact same
    tokens a fresh engine would produce."""
    cfg, params = _family("qwen3-8b")
    eng = PagedServeEngine(cfg, params, slots=2, max_seq=32, page_size=4,
                           n_pages=3, prefill_chunk=16, age_steps=0)
    rng = np.random.default_rng(5)
    p0 = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    eng.submit(Request(0, p0.copy(), 8))       # peak 12 rows = whole pool
    done = list(eng.step())                    # prefill commits first step
    assert not eng._prefills and eng.alloc.n_free == 1
    eng.submit(Request(1, p1.copy(), 4))       # needs 2 free pages to start
    saw_blocked_at_zero = False
    while eng.scheduler.slots[0] is not None:
        assert eng.scheduler.n_active == 1     # r1 never co-admitted
        saw_blocked_at_zero |= (eng.alloc.n_free == 0
                                and eng.scheduler.n_waiting == 1)
        done.extend(eng.step())
    assert saw_blocked_at_zero                 # the pool really hit zero
    while eng.scheduler.has_work():
        done.extend(eng.step())
    assert sorted(r.rid for r in done) == [0, 1]
    ref = ServeEngine(cfg, params, slots=1, max_seq=32)
    ref.submit(Request(1, p1.copy(), 4))
    assert next(r for r in done if r.rid == 1).out == ref.run()[0].out
    assert eng.alloc.n_free == eng.alloc.n_pages   # everything returned


def test_paged_preemption_under_pressure_is_bit_exact():
    """Tight pool + two priority classes: low-priority requests get swapped
    out under page pressure and later resumed.  Every request must still
    produce exactly the tokens a fresh single-request engine produces, and
    same-class completion follows submit order (FIFO requeue)."""
    cfg, params = _family("qwen3-8b")
    rng = np.random.default_rng(9)
    reqs = [Request(i, rng.integers(0, cfg.vocab, size=4).astype(np.int32),
                    4, priority=(0 if i % 2 == 0 else 2)) for i in range(8)]
    ref = _reference_outputs(cfg, params, reqs, max_seq=32)
    eng = PagedServeEngine(cfg, params, slots=4, max_seq=32, page_size=2,
                           n_pages=8, prefill_chunk=4, age_steps=0)
    for r in reqs:
        eng.submit(Request(r.rid, r.prompt.copy(), r.max_new,
                           priority=r.priority))
    done, finish_order = [], []
    while eng.scheduler.has_work():
        for r in eng.step():
            done.append(r)
            finish_order.append(r.rid)
    assert sorted(finish_order) == [r.rid for r in reqs]
    assert eng.preemptions > 0                 # the scenario exercised it
    for r in done:
        assert r.out == ref[r.rid], f"request {r.rid} diverged after " \
            f"{r.preemptions} preemption(s)"
    # equal prompt lengths + equal max_new: within a class, completion
    # order == admission order == submit order (FIFO requeue)
    for cls in (0, 1):
        order = [rid for rid in finish_order if rid % 2 == cls]
        assert order == sorted(order)


def test_commit_time_page_pressure_restarts_prefill_cleanly():
    """Regression: a request whose prefill finishes while the pool is too
    full to commit must requeue as a plain prefill restart.  The old path
    swapped it out through ``_preempt`` with the slot's idle ``pos``
    sentinel (``max_seq`` rows — 16 pages against an 8-page pool, so the
    request could never be admitted again: a livelock with the whole pool
    free), and its already-emitted first token would have been duplicated
    by the rerun.  Long prompts on a tight pool hit this reliably."""
    cfg, params = _family("qwen3-8b")
    reqs = make_requests(cfg, 10, 4, seed=0, long_every=3,
                         priorities=(0, 1, 2))
    ref = _reference_outputs(cfg, params, reqs, max_seq=64)
    eng = PagedServeEngine(cfg, params, slots=3, page_size=4, n_pages=8,
                           prefill_chunk=4)
    for r in reqs:
        eng.submit(Request(r.rid, r.prompt.copy(), r.max_new,
                           priority=r.priority))
    done = []
    for _ in range(200):                       # livelocked forever before
        done.extend(eng.step())
        if len(done) == len(reqs):
            break
    assert len(done) == len(reqs), (
        f"engine stalled: {len(done)}/{len(reqs)} finished, "
        f"{eng.alloc.n_free} pages free")
    assert eng.preemptions > 0                 # pressure actually fired
    for r in done:
        assert r.out == ref[r.rid] and len(r.out) == r.max_new


def test_paged_low_priority_is_not_starved():
    """Sustained high-priority load on one slot: aging must eventually
    admit (and keep, unpreempted) the low-priority request before the
    high-priority stream drains."""
    cfg, params = _family("qwen3-8b")
    eng = PagedServeEngine(cfg, params, slots=1, max_seq=32, page_size=4,
                           prefill_chunk=16, age_steps=4)
    rng = np.random.default_rng(11)
    prompt = lambda: rng.integers(0, cfg.vocab, size=3).astype(np.int32)
    low = Request(100, prompt(), 3, priority=3)
    eng.submit(low)
    finished, rid = [], 0
    for step in range(60):
        if step % 3 == 0 and rid < 10:         # two fresh highs per window
            eng.submit(Request(rid, prompt(), 3, priority=0))
            rid += 1
        finished.extend(eng.step())
        if low.rid in {r.rid for r in finished}:
            break
    assert low.rid in {r.rid for r in finished}, "low priority starved"
    unfinished_high = rid - sum(1 for r in finished if r.rid != low.rid)
    assert unfinished_high > 0 or rid < 10     # it beat part of the stream
    while eng.scheduler.has_work():            # drain; everyone completes
        finished.extend(eng.step())
    assert len(finished) == rid + 1


# ---------------------------------------------------------------------------
# paged engine: the decode and prefill programs own the cache they are given
# ---------------------------------------------------------------------------

def _record_caches(eng):
    """Wrap the engine's batched decode and chunked prefill; returns the
    list of caches the two programs are given."""
    given = []
    for attr in ("_decode_batch", "_decode"):
        def record(params, cache, *rest, _step=getattr(eng, attr)):
            given.append(cache)
            return _step(params, cache, *rest)
        setattr(eng, attr, record)
    return given


def _consumed(cache):
    return all(x.is_deleted() for x in jax.tree_util.tree_leaves(cache))


def test_decode_and_prefill_consume_the_cache_they_are_given():
    """Both programs donate their cache: what the engine passed in is
    deleted once they return, and the engine holds only what they gave
    back."""
    cfg, params = _family("qwen3-8b")
    eng = PagedServeEngine(cfg, params, slots=2, max_seq=32, page_size=4,
                           prefill_chunk=4)
    given = _record_caches(eng)
    eng.submit(Request(0, np.arange(1, 10, dtype=np.int32), 4))
    (done,) = eng.run()
    assert done.status == "OK" and len(done.out) == 4
    assert len(given) == 3 + 3            # three prefill chunks, three steps
    assert all(_consumed(c) for c in given)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(eng.cache))


def test_swap_and_sanitize_run_across_donated_steps():
    """Swap-out reads, and swap-in and commit write, the cache the last
    donated step returned: under page pressure, with the sanitizer checking
    the page table every tick, each request still serves exactly the
    tokens a fresh single-request engine serves."""
    cfg, params = _family("qwen3-8b")
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab, size=5).astype(np.int32),
                    5, priority=(0 if i % 2 == 0 else 2)) for i in range(6)]
    ref = _reference_outputs(cfg, params, reqs, max_seq=32)
    eng = PagedServeEngine(cfg, params, slots=3, max_seq=32, page_size=2,
                           n_pages=9, prefill_chunk=4, age_steps=0,
                           sanitize=True)
    given = _record_caches(eng)
    for r in reqs:
        eng.submit(Request(r.rid, r.prompt.copy(), r.max_new,
                           priority=r.priority))
    done = eng.run()
    assert eng.preemptions > 0                 # swap-out and swap-in ran
    assert sorted(r.rid for r in done) == [r.rid for r in reqs]
    for r in done:
        assert r.out == ref[r.rid], f"request {r.rid} diverged after " \
            f"{r.preemptions} preemption(s)"
    assert all(_consumed(c) for c in given)
