"""jit-compiled continuous-batching step loop + ServeReport.

One decode step = one device call over the whole slot pool: every slot
carries its own position (repro.models decode paths accept a (B,) position
vector) and inactive slots ride along masked — their garbage output is
discarded host-side and their cache is fully overwritten on the next
admission, so correctness never depends on slot hygiene. Prefill runs at
each request's exact prompt length (**no padding** — the canonical padding
discussion lives in docs/serving.md); same-length admissions share one
batched prefill call and each row's cache is scattered into its pool slot.

Greedy continuous decoding is token-identical to single-request decoding
(tests/test_runtime.py): the per-slot valid mask makes every slot's
attention see exactly the KV a lone request would, and batching changes
logits only at float-ulp level, orders of magnitude below argmax gaps.

Known scope limits (documented, enforced): the encoder-decoder (audio)
family keeps a scalar-position decode path and is not served here; MoE
families route per batch, so capacity dropping can couple slots — exact
equivalence needs a high ``moe_capacity_factor`` (same caveat as
tests/test_decode.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import register_engine
from repro.models import build_model
from repro.obs.metrics import (MetricsRegistry, group_percentiles,
                               percentiles)
from repro.obs.trace import null_tracer
from repro.runtime.kvcache import KVCachePool
from repro.runtime.queue import ServeRequest

# the one latency-summary helper (mean/p50/p95/p99/max) now lives in
# repro.obs.metrics; kept under the old private name for callers that
# reached in here.
_percentiles = percentiles


def request_rows(records: Dict[int, Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per-request report rows from engine-style lifecycle records.

    Shared by the continuous engine and the static server so both
    ServeReports carry the identical field set (docs/serving.md)."""
    rows = []
    for rid in sorted(records):
        r = records[rid]
        rows.append({
            "rid": rid, "prompt_len": r["prompt_len"],
            "new_tokens": len(r["tokens"]),
            "arrival_s": round(r["arrival_s"], 6),
            "ttft_ms": (r["first_token_s"] - r["arrival_s"]) * 1e3,
            "latency_ms": (r["done_s"] - r["arrival_s"]) * 1e3,
            "tenant": r.get("tenant", "default"),
            "preemptions": r.get("preemptions", 0),
            "tokens": r["tokens"]})
    return rows


@dataclasses.dataclass
class ServeReport:
    """Per-request latency/TTFT plus aggregate throughput for one run.

    The aggregate percentile blocks (``ttft_ms``/``latency_ms``) mix every
    tenant into one population, which is the single-tenant view old
    consumers expect; multi-tenant runs additionally get a ``per_tenant``
    block (p50/p95/p99 TTFT/latency per tenant plus request/preemption
    counts) and the total ``preemptions`` counter.
    """
    engine: str
    arch: str
    wall_s: float
    num_requests: int
    prefill_tokens: int
    decode_tokens: int
    steps: int
    token_budget: Optional[int]
    max_active: int
    step_active: List[int]
    per_request: List[Dict[str, Any]]
    verified: Optional[Dict[str, Any]] = None   # token-identity audit
    # static server: the whole batch shares one post-prefill TTFT stamp
    # (no per-request admission exists there) — flagged so consumers don't
    # read its ttft percentiles as a distribution.
    ttft_shared: bool = False
    preemptions: int = 0
    tenant_shares: Optional[Dict[str, int]] = None  # last computed shares
    # KV-memory accounting (pool.cache_stats()): capacity/peak bytes,
    # utilization, fragmentation — the slot-pooled vs paged memory story
    # as a measured report field, not an assertion (docs/serving.md).
    cache_utilization: Optional[Dict[str, Any]] = None
    # speculative engine only: windows/proposed/accepted counters,
    # acceptance_rate, tokens_per_step (docs/serving.md).
    speculation: Optional[Dict[str, Any]] = None
    # streaming run only: per-token emission audit (stream order ==
    # final token order, checked in repro.api.serving.audit_stream).
    stream: Optional[Dict[str, Any]] = None

    @property
    def requests_per_s(self) -> float:
        return self.num_requests / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def decode_tok_per_s(self) -> float:
        return self.decode_tokens / self.wall_s if self.wall_s > 0 else 0.0

    def tenant_summary(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant p50/p95/p99 TTFT/latency + request/preempt counts."""
        out = group_percentiles(self.per_request, "tenant",
                                ("ttft_ms", "latency_ms"))
        for tenant, block in out.items():
            rows = [r for r in self.per_request
                    if r.get("tenant", "default") == tenant]
            block["num_requests"] = len(rows)
            block["preemptions"] = sum(r.get("preemptions", 0)
                                       for r in rows)
        return out

    def to_json(self) -> Dict[str, Any]:
        ttft = percentiles([r["ttft_ms"] for r in self.per_request])
        lat = percentiles([r["latency_ms"] for r in self.per_request])
        out = {"engine": self.engine, "arch": self.arch,
                "wall_s": round(self.wall_s, 4),
                "num_requests": self.num_requests,
                "prefill_tokens": self.prefill_tokens,
                "decode_tokens": self.decode_tokens,
                "steps": self.steps,
                "token_budget": self.token_budget,
                "max_active": self.max_active,
                "requests_per_s": round(self.requests_per_s, 2),
                "decode_tok_per_s": round(self.decode_tok_per_s, 2),
                "ttft_ms": ttft, "ttft_shared": self.ttft_shared,
                "latency_ms": lat,
                "preemptions": self.preemptions,
                "per_tenant": self.tenant_summary(),
                "per_request": self.per_request}
        if self.tenant_shares is not None:
            out["tenant_shares"] = self.tenant_shares
        if self.cache_utilization is not None:
            out["cache_utilization"] = self.cache_utilization
        if self.speculation is not None:
            out["speculation"] = self.speculation
        if self.stream is not None:
            out["stream"] = self.stream
        if self.verified is not None:
            out["verified"] = self.verified
        return out

    def summary(self) -> str:
        ttft = percentiles([r["ttft_ms"] for r in self.per_request])
        return (f"[{self.engine}] {self.num_requests} requests in "
                f"{self.wall_s:.2f}s — {self.requests_per_s:.1f} req/s, "
                f"{self.decode_tok_per_s:.1f} decode tok/s, "
                f"ttft p50/p95 {ttft['p50']:.1f}/{ttft['p95']:.1f}ms, "
                f"max_active={self.max_active}"
                + (f"/{self.token_budget}" if self.token_budget else ""))


class _SlotBudgeter:
    """Admission budget for the slot pool: one free slot per request."""

    def __init__(self, pool):
        self._free = pool.num_free

    def can_take(self, req: ServeRequest) -> bool:
        return self._free > 0

    def take(self, req: ServeRequest) -> None:
        self._free -= 1


def _resolve_now(now) -> float:
    """Timestamps are taken *after* the blocking device sync so WallClock
    TTFT/latency include the compute that produced the token; pass a
    callable (e.g. ``clock.now``) to get that, or a float to pin a time."""
    return now() if callable(now) else now


@register_engine("continuous")
class ContinuousEngine:
    """Slot-pool decode engine. The scheduler drives admit()/step().

    VLM configs are served **text-only** (the prompt-only prefill never
    exercises the patches pathway); note the static server instead feeds
    zero patches that occupy real sequence positions, so static-vs-
    continuous outputs are not comparable for vlm archs."""

    def __init__(self, cfg, params=None, *, num_slots: int,
                 slot_len: int, seed: int = 0, model=None, sampling=None):
        self._check_family(cfg)
        self.cfg = cfg
        self.model = model if model is not None else build_model(cfg)
        self.params = (params if params is not None
                       else self.model.init(jax.random.PRNGKey(seed)))
        from repro.runtime.sampling import TokenSampler
        self.sampler = TokenSampler(sampling)
        self.pool = self._make_pool(num_slots, slot_len)
        self._build_device_fns(slot_len)
        p = self.pool.num_slots
        self._rid = np.full(p, -1, np.int64)       # -1 = slot idle
        self._tok = np.zeros(p, np.int32)          # last emitted token
        self._remaining = np.zeros(p, np.int64)    # tokens still to emit
        self._idx = np.zeros(p, np.int32)          # next output token index
        self.metrics = MetricsRegistry()
        self.records: Dict[int, Dict[str, Any]] = {}
        self.steps = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        # Streaming surface: every generated token funnels through
        # _emit_token, so a consumer set here observes tokens in exactly
        # the order the final report carries them — for plain decode and
        # speculative bursts alike (docs/serving.md).
        self.on_token = None           # callable(rid, idx, tok, t_s)
        self._tracer = null_tracer()   # rebound by serve()

    # subclass hooks ------------------------------------------------------
    @staticmethod
    def _check_family(cfg) -> None:
        if cfg.family == "audio":
            raise NotImplementedError(
                "the encoder-decoder family decodes with a scalar position "
                "(learned absolute embeddings) and is not served by the "
                "continuous runtime; use the static server")

    def _make_pool(self, num_slots: int, slot_len: int):
        return KVCachePool(self.model, num_slots, slot_len)

    def _build_device_fns(self, slot_len: int) -> None:
        if self.sampler.greedy:
            def _step(params, cache, tokens, pos):
                # fused decode + greedy pick: one dispatch, no logits
                # transfer
                logits, new_cache = self.model.decode_step(params, cache,
                                                           tokens, pos)
                return (jnp.argmax(logits[:, -1],
                                   axis=-1).astype(jnp.int32), new_cache)
        else:
            def _step(params, cache, tokens, pos, rids, idxs):
                logits, new_cache = self.model.decode_step(params, cache,
                                                           tokens, pos)
                return (self.sampler.sample(logits[:, -1], rids, idxs),
                        new_cache)

        self._decode = jax.jit(_step, donate_argnums=(1,))
        self._prefill = jax.jit(functools.partial(self.model.prefill,
                                                  cache_len=slot_len))
        self._sample_prefill = jax.jit(self.sampler.sample)

    def _run_prefill(self, tokens, plen: int):
        return self._prefill(self.params, {"tokens": tokens})

    def _device_step(self, tokens, pos, active):
        if self.sampler.greedy:
            return self._decode(self.params, self.pool.buffers, tokens,
                                pos)
        rids = jnp.asarray(np.where(active, self._rid, 0).astype(np.int32))
        idxs = jnp.asarray(np.where(active, self._idx, 0).astype(np.int32))
        return self._decode(self.params, self.pool.buffers, tokens, pos,
                            rids, idxs)

    def drain_evicted(self) -> List[ServeRequest]:
        """Resume requests for victims the *engine* evicted mid-step.

        The slot engine never self-evicts (capacity is reserved up front),
        so this is empty here; the paged engine hands back requests it
        preempted to stay inside the page pool and the scheduler requeues
        them."""
        return []

    @classmethod
    def from_spec(cls, cfg, spec, params=None,
                  model=None) -> "ContinuousEngine":
        """Engine sized by a ServeSpec (resolved slots/slot_len/seed);
        pass ``model`` to adopt an already-built module tree for ``cfg``."""
        return cls(cfg, params=params, num_slots=spec.resolved_num_slots(),
                   slot_len=spec.resolved_slot_len(), seed=spec.engine.seed,
                   model=model, sampling=getattr(spec, "sampling", None))

    def serve(self, requests: List[ServeRequest], spec,
              clock=None, tracer=None) -> ServeReport:
        """One spec-driven serving run: scheduler stack from the spec's
        admission/scheduler/clock sub-specs, then drain ``requests``.

        Resets per-request bookkeeping first (compiled functions survive),
        so one engine can serve warmup + timed passes back to back.
        ``tracer`` (repro.obs) receives scheduler-phase and per-request
        lifecycle spans; build it on the same clock for coherent traces.
        """
        from repro.runtime.scheduler import Scheduler
        if self.steps or self.records:
            self.reset()
        sched = Scheduler.from_spec(self, spec, clock=clock, tracer=tracer)
        self._tracer = sched.tracer    # per-token instants on request tracks
        return sched.run(requests)

    def reset(self) -> None:
        """Forget all requests/stats but keep params and compiled fns.

        Lets a benchmark reuse one engine for warmup + timed runs so the
        timed pass measures steady-state serving, not retracing.
        """
        self.pool.reset()
        self._rid[:] = -1
        self._tok[:] = 0
        self._remaining[:] = 0
        self._idx[:] = 0
        self.metrics = MetricsRegistry()
        self.records = {}
        self.steps = self.decode_tokens = self.prefill_tokens = 0

    # ----- capacity -----
    def num_active(self) -> int:
        return int((self._rid >= 0).sum())

    def has_capacity(self) -> bool:
        return self.pool.num_free > 0

    def admission_budgeter(self):
        """Stateful per-loop admission budget the scheduler consults.

        The slot engine's budget is simply the free-slot count; the paged
        engine's additionally requires enough free *pages* for the
        candidate's prompt plus one growth page per already-active request
        (the GPSL fixed-work invariant restated in pages). ``can_take``
        must stay true after ``take`` for every admitted request in the
        same loop iteration — the budgeter tracks its own reservations.
        """
        return _SlotBudgeter(self.pool)

    def active_requests(self) -> List[Dict[str, Any]]:
        """Live (slot-holding) requests: rid, tenant, emitted count.

        The scheduler's tenant bookkeeping and preemption-victim choice
        read this instead of poking slot arrays, so alternative engines
        (and test stubs) only need to mirror this surface.
        """
        out = []
        for slot in np.flatnonzero(self._rid >= 0):
            rid = int(self._rid[slot])
            rec = self.records[rid]
            out.append({"rid": rid,
                        "tenant": rec.get("tenant", "default"),
                        "emitted": len(rec["tokens"])})
        return out

    # ----- admission (prefill) -----
    def admit(self, req: ServeRequest, now) -> None:
        self.admit_batch([req], now)

    def admit_batch(self, reqs: List[ServeRequest], now) -> None:
        """Prefill ``reqs`` at exact prompt lengths and occupy slots.

        Same-length requests share one prefill call, chunked to the fixed
        ``_GROUP_SIZES`` so the set of compiled prefill shapes stays small
        (group × distinct length). The prompt's last-position logits yield
        each request's first generated token, so TTFT is the admit time. A
        max_new_tokens == 1 request completes here and never consumes a
        slot or decode budget.
        """
        by_len: Dict[int, List[ServeRequest]] = {}
        for req in reqs:
            plen = int(req.prompt.shape[0])
            if plen + req.max_new_tokens > self.pool.slot_len:
                raise ValueError(
                    f"request {req.rid}: prompt {plen} + max_new "
                    f"{req.max_new_tokens} exceeds slot capacity "
                    f"{self.pool.slot_len}")
            by_len.setdefault(plen, []).append(req)
        for plen, group in by_len.items():
            i = 0
            while i < len(group):
                g = next(s for s in self._GROUP_SIZES
                         if s <= len(group) - i)
                self._admit_chunk(group[i:i + g], plen, now)
                i += g

    _GROUP_SIZES = (16, 4, 1)

    def _admit_chunk(self, chunk: List[ServeRequest], plen: int,
                     now) -> None:
        t_start = _resolve_now(now)    # prefill begins: enqueue ends here
        tokens = jnp.asarray(np.stack([r.prompt for r in chunk]))
        logits, cache, _ = self._run_prefill(tokens, plen)
        if self.sampler.greedy:
            firsts = np.asarray(jnp.argmax(logits,
                                           axis=-1).astype(jnp.int32))
        else:
            # First tokens from prefill logits through the same keyed
            # sampler as decode. A resuming request's next token index is
            # its emitted count, so its key stream continues unbroken.
            rids = np.asarray([r.rid for r in chunk], np.int32)
            idxs = np.asarray([self._resume_index(r) for r in chunk],
                              np.int32)
            firsts = np.asarray(self._sample_prefill(
                logits, jnp.asarray(rids), jnp.asarray(idxs)))
        t = _resolve_now(now)          # after the sync: TTFT covers prefill
        self.prefill_tokens += plen * len(chunk)
        for row, req in enumerate(chunk):
            first = int(firsts[row])
            rec = self.records.get(req.rid)
            if rec is not None and rec.pop("resume_pending", False):
                # Preempted request resuming: its prompt is the original
                # prompt + everything already emitted, so this prefill's
                # last-position argmax is exactly the token an
                # uninterrupted decode would have produced next. Append
                # to the original record — arrival/TTFT stamps stay.
                self._emit_token(req.rid, first, t)
            else:
                rec = {"rid": req.rid, "prompt_len": plen,
                       "max_new_tokens": req.max_new_tokens,
                       "arrival_s": req.arrival_s,
                       "admit_start_s": t_start,
                       "admit_s": t, "first_token_s": t, "done_s": None,
                       "tenant": req.tenant, "preemptions": 0,
                       "prompt": np.asarray(req.prompt),
                       "tokens": []}
                self.records[req.rid] = rec
                self._emit_token(req.rid, first, t)
            if len(rec["tokens"]) >= rec["max_new_tokens"]:
                rec["done_s"] = t
                continue
            slot = self.pool.alloc()
            if slot is None:
                raise RuntimeError("admit() called with no free slot")
            self.pool.insert(cache, slot, plen, row=row)
            self._rid[slot] = req.rid
            self._tok[slot] = first
            self._remaining[slot] = rec["max_new_tokens"] \
                - len(rec["tokens"])
            self._idx[slot] = len(rec["tokens"])

    def _resume_index(self, req: ServeRequest) -> int:
        """0-based output index of the *next* token for this request —
        the emitted count when it is a resume_pending record, else 0."""
        rec = self.records.get(req.rid)
        if rec is not None and rec.get("resume_pending"):
            return len(rec["tokens"])
        return 0

    def _emit_token(self, rid: int, tok: int, t: float) -> None:
        """The single token-emission path: record append + stream hook.

        Prefill first-tokens, per-step decode tokens, and speculative
        bursts all land here, so the ``on_token`` consumer and the
        per-token trace instants observe exactly the order (and values)
        the final report's ``tokens`` lists carry.
        """
        rec = self.records[rid]
        idx = len(rec["tokens"])
        rec["tokens"].append(tok)
        if self.on_token is not None:
            self.on_token(rid, idx, tok, t)
        if self._tracer.enabled:
            self._tracer.instant("token", cat="request", ts_s=t, rid=rid,
                                 idx=idx, tok=tok)

    def preempt(self, rid: int) -> Dict[str, Any]:
        """Evict an in-flight request: free its KV slot, keep its record.

        The slot returns to the pool immediately (its cache needs no
        scrubbing — insertion overwrites). The record is flagged
        ``resume_pending`` so the next admission of this rid *appends* to
        the emitted tokens instead of restarting the lifecycle. Greedy
        decoding is a pure function of the context, so re-prefilling
        prompt + emitted-prefix resumes token-identically to an
        uninterrupted decode (pinned in tests/test_multitenant.py).
        Returns the record (the scheduler reads ``tokens`` to build the
        resume request).
        """
        slots = np.flatnonzero(self._rid == rid)
        if slots.size == 0:
            raise ValueError(f"request {rid} is not actively decoding")
        slot = int(slots[0])
        self._rid[slot] = -1
        self._remaining[slot] = 0
        self.pool.release(slot)
        rec = self.records[rid]
        rec["preemptions"] = rec.get("preemptions", 0) + 1
        rec["resume_pending"] = True
        return rec

    def warm(self, prompt_lens) -> None:
        """Pre-compile every reachable (group size, prompt length) admission
        shape so a timed run never hits a mid-flight retrace. Group sizes
        beyond the pool can never be admitted, so they are skipped."""
        for plen in sorted(set(int(p) for p in prompt_lens)):
            for g in self._GROUP_SIZES:
                if g <= self.pool.num_slots:
                    self._prefill(self.params,
                                  {"tokens": jnp.zeros((g, plen),
                                                       jnp.int32)})

    # ----- decode -----
    def step(self, now) -> List[int]:
        """One decode step over the pool; returns rids finished this step.
        ``now``: a float timestamp or a callable read after the device sync.

        Inactive slots decode token 0 at position 0 — pure masked padding
        whose output is dropped and whose cache is rewritten on insert.
        """
        active = self._rid >= 0
        n_active = int(active.sum())
        if n_active == 0:
            return []
        tokens = jnp.asarray(np.where(active, self._tok, 0)[:, None])
        pos = jnp.asarray(np.where(active, self.pool.pos, 0).astype(np.int32))
        nxt, new_cache = self._device_step(tokens, pos, active)
        self.pool.swap(new_cache)
        nxt = np.asarray(nxt)
        t = _resolve_now(now)        # after the sync: latency covers decode
        self.steps += 1
        self.decode_tokens += n_active
        finished: List[int] = []
        for slot in np.flatnonzero(active):
            rid = int(self._rid[slot])
            self._emit_token(rid, int(nxt[slot]), t)
            self._tok[slot] = nxt[slot]
            self.pool.pos[slot] += 1
            self._remaining[slot] -= 1
            self._idx[slot] += 1
            if self._remaining[slot] == 0:
                self.records[rid]["done_s"] = t
                self._rid[slot] = -1
                self.pool.release(int(slot))
                finished.append(rid)
        self._observe_cache()
        return finished

    def _observe_cache(self) -> None:
        """Per-step KV-memory gauges (kv_*_in_use, kv_fragmentation) so a
        run's peak/min land in ``metrics.snapshot()`` and, through the
        scheduler's tracer counters, in the live event log."""
        stats = self.pool.cache_stats()
        kind = stats["kind"]
        self.metrics.gauge(f"kv_{kind}s_in_use").set(
            stats[f"{kind}s_in_use"])
        self.metrics.gauge("kv_fragmentation").set(stats["fragmentation"])
        self.metrics.gauge("kv_in_use_bytes").set(stats["in_use_bytes"])

    # ----- reporting -----
    def build_report(self, engine_name: str, wall_s: float,
                     token_budget: Optional[int],
                     step_active: List[int],
                     tenant_shares: Optional[Dict[str, int]] = None
                     ) -> ServeReport:
        per_request = request_rows(self.records)
        stats = self.pool.cache_stats()
        cap = stats["capacity_bytes"]
        stats["utilization"] = (stats["peak_in_use_bytes"] / cap
                                if cap else 0.0)
        return ServeReport(
            engine=engine_name, arch=self.cfg.name, wall_s=wall_s,
            num_requests=len(per_request),
            prefill_tokens=self.prefill_tokens,
            decode_tokens=self.decode_tokens, steps=self.steps,
            token_budget=token_budget,
            max_active=max(step_active, default=0),
            step_active=step_active, per_request=per_request,
            preemptions=sum(r.get("preemptions", 0)
                            for r in self.records.values()),
            tenant_shares=tenant_shares,
            cache_utilization=stats)


@functools.lru_cache(maxsize=32)
def _reference_fns(model, cache_len: int):
    return (jax.jit(functools.partial(model.prefill, cache_len=cache_len)),
            jax.jit(model.decode_step, donate_argnums=(1,)))


def reference_generate(model, params, prompt: np.ndarray,
                       max_new_tokens: int, cache_len: int, *,
                       with_margins: bool = False):
    """Single-request greedy decoding — the runtime's ground truth.

    Exact-length batch-1 prefill followed by one decode step per token, the
    same code path a continuous slot takes, with nothing else in the batch.
    Returns the token list; with ``with_margins`` also the top-1 minus
    top-2 logit margin behind each token (how near a tie each pick was).
    """
    prefill, decode = _reference_fns(model, cache_len)
    margins: List[float] = []

    def pick(row) -> int:
        if with_margins:
            top2 = np.asarray(jax.lax.top_k(row, 2)[0], np.float64)
            margins.append(float(top2[0] - top2[1]))
        return int(jnp.argmax(row))

    logits, cache, pos = prefill(params,
                                 {"tokens": jnp.asarray(prompt[None])})
    toks = [pick(logits[0])]
    posv = jnp.asarray([int(pos)], jnp.int32)
    for _ in range(max_new_tokens - 1):
        tok = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, cache = decode(params, cache, tok, posv)
        posv = posv + 1
        toks.append(pick(logits[0, -1]))
    return (toks, margins) if with_margins else toks
