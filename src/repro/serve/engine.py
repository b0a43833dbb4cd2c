"""ServeEngine: the continuous-batching serving loop.

One engine iteration is a handful of donated-jit dispatches at fixed
shapes — paged mode runs one ``(1, C)`` chunked-prefill dispatch per
prefilling ROW (the page pools have no batch dimension, so prefill cost
tracks real tokens instead of billing every idle row) plus an optional
``(B, 1)`` decode step; dense mode keeps a single ``(B, C)`` prefill
dispatch.  Either way the whole serving lifetime compiles exactly twice
(the PR 2/6 fused-step idiom: model step + sampling + cache update in
one dispatch, cache donated).  Rows not participating in a dispatch
carry ``pos = max_seq``: their writes drop (dense) or land on the
reserved scratch page (paged), and their outputs are ignored.

Sampling is keyed per REQUEST, not per step:
``fold_in(fold_in(key(seed), rid), token_index)`` — so a request's token
stream is independent of scheduling, batch composition, row assignment,
and cache layout.  That is what makes paged-vs-dense generation
bit-exact and preemption's recompute-on-restart produce identical
outputs (tests/test_serve.py pins both).

Latency accounting: TTFT is measured from the moment a request becomes
eligible (its ``arrival`` step reached) to its first sampled token; TPOT
is the mean inter-token time over the remaining tokens.  Results use the
``api.make_serve_result`` schema — absent counters read 0, never
missing, like the training ``RESULT_KEYS``.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.runner import make_serve_result
from repro.launch.steps import request_keys, sample_tokens
from repro.models import transformer as tf
from repro.serve.blocks import BlockAllocator, CacheExhausted, RowTables
from repro.serve.scheduler import Request, Scheduler, ServeConfig

PyTree = Any


def engine_refusal(model) -> str | None:
    """Why ``ServeEngine`` cannot serve ``model``, or None when it can.
    Callers choose their serving path from this before they build
    anything."""
    cfg = model.cfg
    if cfg.family not in ("dense", "moe"):
        return f"ServeEngine serves dense/moe models, not {cfg.family}"
    if cfg.attn_logit_softcap:
        return "ServeEngine does not support logit softcap"
    if any(tf.local_params(cfg, kind)[0] for kind in model.kinds):
        return "ServeEngine requires uniform global attention"
    return None


class ServeEngine:
    """Continuous-batching engine over a dense or paged KV cache.

    ``paged=True`` (default) runs the block-table path over the page
    pools from ``Model.init_paged_cache``; ``paged=False`` runs the same
    scheduler over a plain ``(B, max_seq)`` dense cache — the
    equivalence baseline (both produce bit-identical tokens).
    """

    def __init__(self, model, params: PyTree, cfg: ServeConfig,
                 paged: bool = True):
        reason = engine_refusal(model)
        if reason:
            raise ValueError(reason)
        cfg.validate()
        self.model = model
        self.params = params
        self.cfg = cfg
        self.paged = paged
        self._build_steps()
        self.reset()

    # ------------------------------------------------------------- jitted

    def _build_steps(self) -> None:
        model, cfg = self.model, self.cfg
        temperature, top_k, seed = cfg.temperature, cfg.top_k, cfg.seed

        def decode(params, cache, tokens, pos, tables, rids, tok_idx):
            logits, _values, cache = model.decode_step(
                params, cache, tokens, pos, tables
            )
            keys = request_keys(seed, rids, tok_idx)
            nxt = sample_tokens(
                logits[:, 0], keys, temperature=temperature, top_k=top_k
            )
            return nxt, cache

        def prefill(params, cache, tokens, pos, lens, tables, rids, tok_idx):
            logits, _values, cache = model.prefill_step(
                params, cache, tokens, pos, tables
            )
            # the logits of each row's LAST real chunk token sample the
            # first generated token (rows not finishing ignore theirs)
            last = jnp.maximum(lens - 1, 0)
            lg = jnp.take_along_axis(logits, last[:, None, None], axis=1)
            keys = request_keys(seed, rids, tok_idx)
            nxt = sample_tokens(
                lg[:, 0], keys, temperature=temperature, top_k=top_k
            )
            return nxt, cache

        self._decode = jax.jit(decode, donate_argnums=(1,))
        # paged prefill dispatches per ROW at a fixed (1, C) shape — the
        # page pools have no batch dimension, so a one-row chunk writes
        # straight into the row's pages and prefill cost tracks REAL
        # tokens (a (B, C) dispatch would bill every idle row).  Dense
        # prefill keeps the (B, C) shape: the (B, S) cache rows are baked
        # into the dispatch, and dense mode is the correctness baseline,
        # not the throughput path.
        self._prefill = jax.jit(prefill, donate_argnums=(1,))

    # -------------------------------------------------------------- state

    def reset(self) -> None:
        """Fresh serving state (cache zeroed, queue/counters cleared);
        the compiled steps are reused across resets."""
        cfg = self.cfg
        if self.paged:
            self.cache, _ = self.model.init_paged_cache(
                cfg.num_blocks, cfg.block_size
            )
            self.allocator = BlockAllocator(cfg.num_blocks)
            self.tables = RowTables(
                cfg.batch_rows, cfg.blocks_per_row, cfg.block_size,
                self.allocator,
            )
        else:
            self.cache, _ = self.model.init_cache(cfg.batch_rows, cfg.max_seq)
            self.allocator = None
            self.tables = None
        self.scheduler = Scheduler(cfg)
        self.steps = 0
        self.prefill_chunks = 0
        self.tokens_prefilled = 0
        self.tokens_decoded = 0
        self.queue_depth_peak = 0
        self._occupancy: list[float] = []
        self._eligible_t: dict[int, float] = {}
        self._first_t: dict[int, float] = {}
        self._finish_t: dict[int, float] = {}
        self._gen_counts: dict[int, int] = {}

    # -------------------------------------------------------------- serve

    def submit(self, req: Request) -> None:
        if self.paged:
            need = (len(req.prompt) + req.max_new_tokens - 2) \
                // self.cfg.block_size + 1
            if need > self.cfg.num_blocks - 1:
                raise ValueError(
                    f"request {req.rid} needs {need} pages; the pool has "
                    f"{self.cfg.num_blocks - 1} allocatable"
                )
        self.scheduler.submit(req)

    def _ensure_pages(self, plan) -> None:
        for row in plan.prefill_rows:
            through = int(plan.prefill_pos[row] + plan.prefill_len[row]) - 1
            self.tables.ensure(row, through)
        for row in plan.decode_rows:
            self.tables.ensure(row, int(plan.decode_pos[row]))

    def _plan_with_preemption(self):
        """Plan the step; on cache exhaustion preempt the youngest active
        request (releasing its pages) and replan.  A lone request always
        fits (checked at submit), so this terminates."""
        while True:
            plan = self.scheduler.plan_step()
            if not self.paged:
                return plan
            try:
                self._ensure_pages(plan)
                return plan
            except CacheExhausted:
                victim = self.scheduler.preempt_youngest()
                if victim is None:
                    raise
                self.tables.release(victim[0])

    def step(self) -> None:
        """One engine iteration: admit -> plan (preempting under cache
        pressure) -> at most one prefill dispatch + one decode dispatch
        -> evict finished rows."""
        now = self.steps
        t_now = time.monotonic()
        for req in list(self.scheduler._queue):
            if req.arrival <= now:
                self._eligible_t.setdefault(req.rid, t_now)
        self.scheduler.admit(now)
        self.queue_depth_peak = max(self.queue_depth_peak,
                                    self.scheduler.pending)
        plan = self._plan_with_preemption()
        tables = jnp.asarray(self.tables.as_array()) if self.paged else None

        if plan.prefill_rows:
            pt = jnp.asarray(plan.prefill_tokens)
            pp = jnp.asarray(plan.prefill_pos)
            pl = jnp.asarray(plan.prefill_len)
            rids = jnp.asarray(plan.rids)
            ti = jnp.asarray(plan.tok_idx)
            if self.paged:
                outs = []
                for row in plan.prefill_rows:
                    sl = slice(row, row + 1)
                    nxt, self.cache = self._prefill(
                        self.params, self.cache, pt[sl], pp[sl], pl[sl],
                        tables[sl], rids[sl], ti[sl],
                    )
                    outs.append((row, nxt))
                    self.prefill_chunks += 1
                sampled = np.zeros((self.cfg.batch_rows,), np.int32)
                for row, nxt in outs:
                    sampled[row] = int(np.asarray(nxt)[0])
            else:
                nxt, self.cache = self._prefill(
                    self.params, self.cache, pt, pp, pl, tables, rids, ti,
                )
                sampled = np.asarray(nxt)
                self.prefill_chunks += 1
            finished = self.scheduler.record_prefill(plan, sampled)
            t = time.monotonic()
            for row in finished:
                self._first_t.setdefault(int(plan.rids[row]), t)
            self.tokens_prefilled += int(plan.prefill_len.sum())

        if plan.decode_rows:
            nxt, self.cache = self._decode(
                self.params, self.cache,
                jnp.asarray(plan.decode_tokens),
                jnp.asarray(plan.decode_pos),
                tables,
                jnp.asarray(plan.rids), jnp.asarray(plan.tok_idx),
            )
            self.scheduler.record_decode(plan, np.asarray(nxt))
            self.tokens_decoded += len(plan.decode_rows)

        t = time.monotonic()
        for row in self.scheduler.evict_finished():
            if self.paged:
                self.tables.release(row)
        for rid, toks in self.scheduler.completed.items():
            if rid not in self._finish_t:
                self._finish_t[rid] = t
                self._gen_counts[rid] = len(toks)
        if self.paged:
            self._occupancy.append(self.tables.occupancy())
        else:
            self._occupancy.append(
                len(self.scheduler.active) / self.cfg.batch_rows
            )
        self.steps += 1

    def run(self, requests=None, max_steps: int = 100_000) -> dict:
        """Serve ``requests`` (plus anything already queued) to
        completion and return the ``make_serve_result`` dict."""
        for req in requests or ():
            self.submit(req)
        t0 = time.monotonic()
        while not self.scheduler.idle:
            if self.steps >= max_steps:
                raise RuntimeError(f"serve loop exceeded {max_steps} steps")
            self.step()
        return self.result(seconds=time.monotonic() - t0)

    # ------------------------------------------------------------- result

    def _percentiles(self) -> dict[str, float]:
        ttft = [self._first_t[r] - self._eligible_t.get(r, self._first_t[r])
                for r in self._first_t]
        tpot = [
            (self._finish_t[r] - self._first_t[r]) / (self._gen_counts[r] - 1)
            for r in self._finish_t
            if r in self._first_t and self._gen_counts.get(r, 0) > 1
        ]
        out = {}
        for name, xs in (("ttft", ttft), ("tpot", tpot)):
            out[f"{name}_p50"] = float(np.percentile(xs, 50)) if xs else 0.0
            out[f"{name}_p95"] = float(np.percentile(xs, 95)) if xs else 0.0
        return out

    def result(self, seconds: float = 0.0) -> dict:
        occ = self._occupancy
        return make_serve_result(
            outputs=dict(self.scheduler.completed),
            seconds=seconds,
            completed=len(self.scheduler.completed),
            admitted=self.scheduler.admitted,
            preempted=self.scheduler.preempted,
            steps=self.steps,
            prefill_chunks=self.prefill_chunks,
            tokens_prefilled=self.tokens_prefilled,
            tokens_decoded=self.tokens_decoded,
            queue_depth_peak=self.queue_depth_peak,
            cache_occupancy_peak=max(occ) if occ else 0.0,
            cache_occupancy_mean=float(np.mean(occ)) if occ else 0.0,
            **self._percentiles(),
        )
