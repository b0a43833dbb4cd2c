"""PR 10 serving stack: paged KV blocks, the continuous-batching
scheduler, seeded sampling, and the ServeEngine.

The load-bearing pins:

  * paged-vs-dense bit-exactness — the same prompts/seeds produce
    IDENTICAL token streams through block tables and through the dense
    ``(B, max_seq)`` cache, including after pages cycle through the
    free list (the mixed workload needs 16 pages total against a
    12-page pool, so later requests always run on recycled blocks);
  * the scheduler chaos test — staggered arrivals + a pool tight enough
    to force cache-pressure preemption still completes every request
    with outputs identical to the unpressured dense run
    (recompute-on-restart + per-request sampling streams);
  * sampling determinism — a request's tokens are a function of
    ``(seed, rid, token index)`` only, never of row/batch placement.
"""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.launch.steps import request_keys, sample_tokens
from repro.serve import (
    BlockAllocator,
    CacheExhausted,
    Request,
    RowTables,
    Scheduler,
    ServeConfig,
    ServeEngine,
)

# ------------------------------------------------------------------ blocks


def test_allocator_lifo_reserves_scratch_page():
    """Page 0 is the reserved scratch target for out-of-range writes: it
    is never handed out, and releasing it is an error.  Frees are LIFO so
    page layouts replay deterministically."""
    alloc = BlockAllocator(5)
    assert [alloc.alloc() for _ in range(4)] == [1, 2, 3, 4]
    assert alloc.free_blocks == 0 and alloc.used_blocks == 4
    with pytest.raises(CacheExhausted):
        alloc.alloc()
    alloc.release(3)
    alloc.release(2)
    assert alloc.alloc() == 2  # LIFO: last freed, first reused
    with pytest.raises(ValueError):
        alloc.release(0)


def test_row_tables_grow_release_and_occupancy():
    alloc = BlockAllocator(6)
    tables = RowTables(batch_rows=2, blocks_per_row=3, block_size=4,
                       allocator=alloc)
    tables.ensure(0, 0)      # slot 0 -> 1 page
    tables.ensure(0, 7)      # slots through 7 -> 2 pages
    tables.ensure(1, 3)
    arr = tables.as_array()
    assert arr.shape == (2, 3)
    assert arr[0, 0] != 0 and arr[0, 1] != 0 and arr[0, 2] == 0
    assert tables.occupancy() == pytest.approx(3 / 5)
    with pytest.raises(ValueError):
        tables.ensure(0, 12)  # past blocks_per_row * block_size
    tables.release(0)
    assert alloc.used_blocks == 1
    assert tables.as_array()[0].tolist() == [0, 0, 0]


# --------------------------------------------------------------- scheduler


def _cfg(**kw) -> ServeConfig:
    base = dict(batch_rows=2, prefill_chunk=4, token_budget=3,
                block_size=8, num_blocks=9, max_seq=32)
    base.update(kw)
    return ServeConfig(**base)


def test_request_and_config_validation():
    with pytest.raises(ValueError):
        Request(rid=1, prompt=(), max_new_tokens=1)
    with pytest.raises(ValueError):
        Request(rid=1, prompt=(1,), max_new_tokens=0)
    with pytest.raises(ValueError):
        _cfg(token_budget=0).validate()
    with pytest.raises(ValueError):
        _cfg(max_seq=30).validate()  # not a multiple of block_size
    sched = Scheduler(_cfg())
    with pytest.raises(ValueError):  # needs L + max_new - 1 = 33 slots
        sched.submit(Request(rid=1, prompt=tuple(range(30)),
                             max_new_tokens=4))


def test_scheduler_budget_splits_decode_first_then_chunked_prefill():
    """Sarathi interleaving: every decode row costs one budget token up
    front; the remainder goes to prefill chunks of at most C tokens."""
    sched = Scheduler(_cfg())
    sched.submit(Request(rid=1, prompt=tuple(range(6)), max_new_tokens=2))
    sched.submit(Request(rid=2, prompt=tuple(range(8)), max_new_tokens=2))
    assert sched.admit(now=0) == [1, 2]

    plan = sched.plan_step()  # budget 3: row 0 gets a 3-token chunk
    assert plan.prefill_rows == [0] and plan.decode_rows == []
    assert plan.prefill_len.tolist() == [3, 0]
    assert plan.prefill_pos[0] == 0 and plan.rids.tolist() == [1, 2]
    sched.record_prefill(plan, np.zeros(2, np.int32))

    plan = sched.plan_step()  # row 0 finishes (3 left), samples token 1
    assert plan.finish_rows == [0] and plan.tok_idx[0] == 0
    sched.record_prefill(plan, np.array([7, 0], np.int32))

    plan = sched.plan_step()  # row 0 decodes (priority), row 1 gets 3-1=2
    assert plan.decode_rows == [0] and plan.prefill_rows == [1]
    assert plan.decode_tokens[0, 0] == 7 and plan.decode_pos[0] == 6
    assert plan.tok_idx[0] == 1 and plan.prefill_len[1] == 2


def test_scheduler_seeded_admission_is_deterministic():
    reqs = [Request(rid=r, prompt=(1, 2), max_new_tokens=1)
            for r in (1, 2, 3, 4, 5)]
    expect = sorted((1, 2, 3, 4, 5),
                    key=lambda r: zlib.crc32(f"9:{r}".encode()))
    orders = []
    for _ in range(2):
        sched = Scheduler(_cfg(batch_rows=5, seed=9,
                               shuffle_admissions=True))
        for r in reqs:
            sched.submit(r)
        orders.append(sched.admit(now=0))
    assert orders[0] == orders[1] == expect
    # default is plain FIFO
    sched = Scheduler(_cfg(batch_rows=5))
    for r in reqs:
        sched.submit(r)
    assert sched.admit(now=0) == [1, 2, 3, 4, 5]


def test_scheduler_preempts_youngest_and_requeues_front():
    sched = Scheduler(_cfg())
    for r in (1, 2, 3):
        sched.submit(Request(rid=r, prompt=(1, 2), max_new_tokens=1))
    assert sched.admit(now=0) == [1, 2]
    row, rid = sched.preempt_youngest()
    assert rid == 2 and row == 1 and sched.preempted == 1
    # the preempted request re-enters BEFORE the never-admitted rid 3
    assert sched.admit(now=0) == [2]
    assert [r.rid for r in sched._queue] == [3]


# ---------------------------------------------------------------- sampling


def test_sampling_keyed_by_request_not_row():
    """ISSUE 10 bugfix pin: the serve step's sampling is seeded per
    ``(seed, rid, token index)`` — moving a request to a different batch
    row (as continuous batching constantly does) cannot change its
    tokens."""
    logits = jax.random.normal(jax.random.key(0), (4, 64))
    rids = jnp.array([11, 22, 33, 44])
    idx = jnp.array([0, 1, 2, 3])
    toks = sample_tokens(logits, request_keys(7, rids, idx),
                         temperature=0.7, top_k=8)
    perm = jnp.array([2, 0, 3, 1])
    toks_p = sample_tokens(logits[perm],
                           request_keys(7, rids[perm], idx[perm]),
                           temperature=0.7, top_k=8)
    assert jnp.array_equal(toks_p, toks[perm])
    # different seed, different stream (for this draw)
    toks_s = sample_tokens(logits, request_keys(8, rids, idx),
                           temperature=0.7, top_k=8)
    assert not jnp.array_equal(toks_s, toks)


def test_sampling_greedy_default_and_topk_one():
    logits = jax.random.normal(jax.random.key(1), (3, 32))
    keys = request_keys(0, jnp.array([1, 2, 3]), jnp.array([0, 0, 0]))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert jnp.array_equal(sample_tokens(logits, keys), greedy)
    assert jnp.array_equal(
        sample_tokens(logits, keys, temperature=2.0, top_k=1), greedy
    )


# ------------------------------------------------------------------ result


def test_make_serve_result_schema_absent_as_zero():
    res = api.make_serve_result(outputs={1: [2, 3]}, seconds=2.0,
                                tokens_prefilled=10, tokens_decoded=10)
    assert set(api.SERVE_RESULT_KEYS) <= set(res)
    assert res["preempted"] == 0 and res["ttft_p50"] == 0.0
    assert res["tokens_per_s"] == pytest.approx(10.0)
    with pytest.raises(TypeError):
        api.make_serve_result(outputs={}, seconds=1.0, bogus=1)


# ------------------------------------------------------------------ engine


def test_engine_rejects_unpageable_families():
    from repro.configs.base import get_reduced_config
    from repro.models.model import make_model

    model = make_model(get_reduced_config("mamba2-1.3b"))
    with pytest.raises(ValueError):
        ServeEngine(model, None, ServeConfig())


@pytest.mark.parametrize(
    "arch,served",
    [("qwen2-1.5b", True), ("deepseek-moe-16b", True),
     ("mamba2-1.3b", False), ("recurrentgemma-2b", False),
     ("gemma3-4b", False)],
)
def test_engine_refusal_decides_the_serving_path(arch, served):
    """The launcher picks engine or static loop from ``engine_refusal``
    before it builds anything; the engine refuses exactly what it names."""
    from repro.configs.base import get_reduced_config
    from repro.models.model import make_model
    from repro.serve.engine import engine_refusal

    model = make_model(get_reduced_config(arch))
    reason = engine_refusal(model)
    assert (reason is None) == served, reason
    if not served:
        with pytest.raises(ValueError, match=reason):
            ServeEngine(model, None, ServeConfig())


@pytest.fixture(scope="module")
def small_lm():
    from repro.configs.base import get_config
    from repro.models.model import make_model

    cfg = dataclasses.replace(
        get_config("qwen2-1.5b"), num_layers=2, d_model=128, num_heads=4,
        num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512, remat="none",
    )
    model = make_model(cfg, unroll=True)
    return model, model.init(jax.random.key(0))


def _mixed_requests():
    """7 requests, staggered arrivals, mixed prompt/gen lengths.  Page
    demand sums to 16 blocks against the 12-page pool below, so the free
    list necessarily recycles pages mid-run."""
    key = jax.random.key(3)
    spec = [(5, 4, 0), (12, 6, 0), (3, 8, 1), (17, 3, 2), (9, 5, 4),
            (6, 7, 5), (14, 4, 6)]
    reqs = []
    for i, (L, g, arrival) in enumerate(spec):
        toks = jax.random.randint(jax.random.fold_in(key, i), (L,), 0, 512)
        reqs.append(Request(rid=i + 1, prompt=tuple(int(t) for t in toks),
                            max_new_tokens=g, arrival=arrival))
    return reqs


def _engine_cfg(num_blocks: int) -> ServeConfig:
    return ServeConfig(batch_rows=3, prefill_chunk=8, token_budget=11,
                       block_size=8, num_blocks=num_blocks, max_seq=32,
                       temperature=0.8, top_k=8, seed=42)


@pytest.fixture(scope="module")
def dense_outputs(small_lm):
    model, params = small_lm
    return ServeEngine(model, params, _engine_cfg(13),
                       paged=False).run(_mixed_requests())


@pytest.mark.slow
def test_paged_generation_bitexact_with_dense_after_block_reuse(
        small_lm, dense_outputs):
    """ISSUE 10 acceptance pin: identical token streams through block
    tables and the dense cache — on a workload whose page demand (16)
    exceeds the pool (12), so reuse from the free list is exercised."""
    model, params = small_lm
    engine = ServeEngine(model, params, _engine_cfg(13), paged=True)
    res = engine.run(_mixed_requests())
    assert res["outputs"] == dense_outputs["outputs"]
    for req in _mixed_requests():
        assert len(res["outputs"][req.rid]) == req.max_new_tokens
    assert engine.allocator.used_blocks == 0  # every page released


@pytest.mark.slow
def test_chaos_staggered_arrivals_with_cache_pressure(
        small_lm, dense_outputs):
    """ISSUE 10 acceptance pin: a pool tight enough to force preemption
    (5 usable pages for requests needing up to 3 each) still completes
    every request, with outputs identical to the unpressured dense run —
    recompute-on-restart replays the same per-request sampling streams."""
    model, params = small_lm
    res = ServeEngine(model, params, _engine_cfg(6),
                      paged=True).run(_mixed_requests())
    assert res["preempted"] > 0
    assert res["completed"] == 7
    assert res["outputs"] == dense_outputs["outputs"]


@pytest.mark.slow
def test_engine_counters_and_reset_determinism(small_lm):
    model, params = small_lm
    reqs = _mixed_requests()
    engine = ServeEngine(model, params, _engine_cfg(13), paged=True)
    first = engine.run(reqs)
    engine.reset()
    second = engine.run(reqs)  # compiled steps reused, same tokens
    assert first["outputs"] == second["outputs"]
    assert first["tokens_prefilled"] == sum(len(r.prompt) for r in reqs)
    assert first["tokens_decoded"] == sum(
        r.max_new_tokens - 1 for r in reqs
    )
    assert first["completed"] == 7 and first["steps"] > 0
    assert first["prefill_chunks"] > 0
    assert 0 < first["cache_occupancy_mean"] <= \
        first["cache_occupancy_peak"] <= 1
    assert first["ttft_p95"] >= first["ttft_p50"] >= 0
