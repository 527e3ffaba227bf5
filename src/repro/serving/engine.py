"""Serving engine facade: Scheduler -> KVCache -> ModelRunner composition.

The engine is deliberately thin (DESIGN.md §3): the **Scheduler** owns
admission policy and request lifecycle, the **KVCache** owns device cache
memory (paged block-table pool by default, contiguous oracle behind
``cache_layout=``), and the **ModelRunner** owns the weights plus the
compiled-specialization table.  The facade composes one step of each per
iteration:

    admit -> one [B, chunk] chunked-prefill step -> one [B] decode step

so every prompt, whatever its length, runs through a single fixed-width
prefill graph, concurrent prefills batch together, and decode advances all
live slots at once.  Stacks with mamba blocks (no position dim to page or
chunk) transparently fall back to the contiguous layout with per-request
whole-prompt prefill.

Under the default on-demand reservation discipline (DESIGN.md §6)
admission takes only the prompt's pages, decode grows a slot page by page
as it crosses page boundaries, and a dry pool preempts the last-admitted
live request: its pages are released and it re-queues PREEMPTED, to be
re-prefilled (prompt + generated-so-far) and resumed token-exactly when
pages free up.  ``preemption=False`` restores whole-lifetime reservation
(admission takes prompt + max_new up front; nothing is ever evicted).

The engine loop is **continuous and arrival-aware** (DESIGN.md §9):
``submit(req, arrival_time=)`` enqueues a request onto a time-ordered
arrival queue, ``step()`` releases due arrivals and advances every live
slot one iteration (returning any requests that completed *that step*),
and ``drain()`` steps until the system is empty.  Requests therefore
enter while others are mid-prefill or mid-decode, stream incrementally,
and complete individually -- the open-loop serving regime.  Time comes
from one injected clock: the monotonic wall clock by default, or a
deterministic ``VirtualClock`` (one tick per step) so tests can script
arrival patterns exactly.  ``serve(reqs)`` survives as a thin
closed-loop wrapper: submit everything at t=now, drain, report.

``Engine(cfg, params).serve(reqs)`` is unchanged from the monolith it
replaced; ``serve(reqs, plan="name")`` after ``add_plan`` serves a LExI
plan from the same runner and weights.

The expert budget is a **per-request resource** (DESIGN.md §10): each
``Request`` may carry its own registered plan name, resolved at submit
against the serve default, and heterogeneous-plan requests pack into one
batch.  A step whose live slots share a plan runs that plan's exact
static-k graph; a mixed step runs a bucketed-k graph (per-layer max k,
pow2 roundup) with surplus routed slots zero-weighted -- bitwise the
numerics of each slot's own plan.  Under pool/queue pressure the engine
can walk non-priority requests down a declared plan ladder
(``set_plan_ladder`` + ``degrade_under_pressure=True``), one rung per
(re-)admission -- a plan switch always rides the prefill boundary, since
the per-request prefix-cache salt makes the old rung's pages a miss.
"""

from __future__ import annotations

import heapq
import math
from dataclasses import replace
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ModelConfig
from repro.models.attention import cache_buf_len
from repro.models.opts import DEFAULT_OPTS, ModelOpts
from repro.serving.clock import Clock, WallClock
from repro.serving.detok import IncrementalDetok
from repro.serving.kv_cache import KVCache
from repro.serving.request import Request, Result
from repro.serving.runner import BASE_PLAN, ModelRunner
from repro.serving.sampling import sample_per_slot
from repro.serving.scheduler import DECODE, DONE, PREFILL, Scheduler, \
    Tracked, duplicate_uid_error
from repro.serving.trace import CPU_KEY, PHASES, WALL_PREFIX, phase

_CHUNKABLE_KINDS = ("attn_mlp", "attn_moe", "shared_attn")

#: admission-gate policies for on-demand paged admission (DESIGN.md §11):
#: how many free pages an admission must leave behind for the slots
#: already decoding, so admitting a newcomer does not just preempt it
#: right back out (admit -> evict -> recompute churn)
ADMISSION_POLICIES = ("headroom", "watermark", "lookahead", "greedy")


def _plan_label(plan: str, bucket) -> str:
    """A step's plan for its span: the plan's name, or its k bucket."""
    return plan if bucket is None else "bucket:" + "-".join(map(str, bucket))


def _supports_paging(cfg: ModelConfig) -> bool:
    return (not cfg.is_encoder_decoder
            and all(b.kind in _CHUNKABLE_KINDS for b in cfg.pattern()))


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_len: int = 512, prefill_pad: int = 64,
                 prefill_chunk: Optional[int] = None,
                 cache_layout: Optional[str] = None,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 use_kernel: Optional[bool] = None,
                 use_moe_decode: Optional[bool] = None,
                 expert_dtype: Optional[str] = None,
                 router_lookahead: Optional[bool] = None,
                 preemption: Optional[bool] = None,
                 prefix_cache: bool = False,
                 scheduler: str = "fifo",
                 admission: str = "headroom",
                 admission_watermark: float = 0.25,
                 truncate_prompts: bool = False,
                 degrade_under_pressure: bool = False,
                 degrade_watermark: float = 0.25,
                 eos_id: Optional[int] = None, opts: ModelOpts = DEFAULT_OPTS,
                 clock: Optional[Clock] = None, mesh=None, seed: int = 0):
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefill_pad = prefill_pad
        # engine-wide *default* stop token: a Request.eos_id overrides it
        # per request, so requests with different stop tokens share a batch
        self.eos_id = eos_id
        self.truncate_prompts = truncate_prompts
        self.key = jax.random.PRNGKey(seed)
        # one clock seam for every latency interval (engine + scheduler):
        # monotonic perf_counter by default, VirtualClock for
        # deterministic arrival-pattern tests (one tick per engine step)
        self.clock = clock if clock is not None else WallClock()

        pageable = _supports_paging(cfg)
        if cache_layout is None:
            cache_layout = "paged" if pageable else "contiguous"
        if cache_layout == "paged" and not pageable:
            raise ValueError(
                f"{cfg.name}: paged KV / chunked prefill need an "
                "attention-only stack; use cache_layout='contiguous'")
        if prefill_chunk is not None and prefill_chunk > 0 and not pageable:
            raise ValueError(f"{cfg.name}: chunked prefill needs an "
                             "attention-only stack")
        # prefill_chunk=0 forces the legacy whole-prompt [1, L] prefill
        # (jit per padded length; contiguous layout only)
        self.chunked = pageable and prefill_chunk != 0
        if cache_layout == "paged" and not self.chunked:
            raise ValueError("whole-prompt prefill (prefill_chunk=0) writes "
                             "through slot scatter; use cache_layout="
                             "'contiguous'")
        # in-kernel paged decode (block-table-native flash-decode); the
        # gather path stays as the equivalence oracle when False
        self.use_kernel = (opts.use_paged_kernel if use_kernel is None
                           else bool(use_kernel))
        if self.use_kernel and cache_layout != "paged":
            raise ValueError("use_kernel=True walks block tables; it needs "
                             "cache_layout='paged'")
        # decode-regime MoE: fused routed-expert dispatch on decode steps
        # (models/moe/decode.py); the gmm path stays the oracle when False.
        # Layout-independent -- it switches the MoE layer impl, not the KV.
        self.use_moe_decode = (opts.use_moe_decode_kernel
                               if use_moe_decode is None
                               else bool(use_moe_decode))
        # on-demand page reservation + preemption (None -> on for paged).
        # preemption=False is the whole-lifetime-reservation baseline: an
        # admitted request can always complete, but a single long-max_new
        # request blocks pool capacity it may never use.
        if preemption is None:
            preemption = cache_layout == "paged"
        if preemption and cache_layout != "paged":
            raise ValueError("preemption manages the paged pool; it needs "
                             "cache_layout='paged'")
        self.ondemand = bool(preemption)
        # admission gate policy (DESIGN.md §11): what an on-demand
        # admission must leave free for the already-decoding slots
        if admission not in ADMISSION_POLICIES:
            raise ValueError(f"admission={admission!r}; "
                             f"want one of {ADMISSION_POLICIES}")
        if admission != "headroom" and not self.ondemand:
            raise ValueError("admission policies gate on-demand paged "
                             "admission; they need preemption=True "
                             "(whole-lifetime reservation never over-admits)")
        self.admission = admission
        self.admission_watermark = float(admission_watermark)
        # prefix caching (DESIGN.md §8): hash-cons full KV pages so a new
        # request's admission maps already-computed prefix pages into its
        # block table and chunked prefill starts at the first uncached
        # position.  Needs the paged layout (page granularity is the
        # sharing unit), the on-demand discipline (whole-lifetime
        # reservation never releases pages early enough to share), and no
        # ring wrap (a sliding-window ring rewrites pages in place, so a
        # cached page's content would not stay the pure function of its
        # token prefix the index key asserts).  Mamba stacks are excluded
        # transitively: they force the contiguous layout.
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache:
            if cache_layout != "paged":
                raise ValueError("prefix_cache shares pages; it needs "
                                 "cache_layout='paged'")
            if not self.ondemand:
                raise ValueError("prefix_cache needs the on-demand "
                                 "reservation discipline (preemption=True)")
            if cache_buf_len(cfg, max_len) < max_len:
                raise ValueError(
                    "prefix_cache cannot serve a sliding-window ring "
                    f"(cache_buf_len={cache_buf_len(cfg, max_len)} < "
                    f"max_len={max_len}): wrapped pages are rewritten in "
                    "place, so cached content would go stale")
        # cap at the ring size: a chunk wider than the window would scatter
        # two positions into one ring slot within a single write
        self.prefill_chunk = (min(prefill_chunk or prefill_pad,
                                  cache_buf_len(cfg, max_len))
                              if self.chunked else 0)

        # Quantized expert tiles: quantize at load so the engine never
        # holds both weight copies, and bake the dtype into opts -- it
        # joins every runner specialization key, so bf16 and quantized
        # engines never share a compiled graph.
        from repro.models.moe import QUANT_DTYPES, quantize_expert_params
        ed = opts.expert_dtype if expert_dtype is None else expert_dtype
        if ed not in ("bf16",) + QUANT_DTYPES:
            raise ValueError(f"expert_dtype={ed!r}; want 'bf16' or one of "
                             f"{QUANT_DTYPES}")
        if ed != "bf16":
            impl = opts.moe_impl or cfg.moe_impl
            if not cfg.is_moe or impl not in ("gmm", "decode"):
                raise ValueError(
                    f"expert_dtype={ed!r} is served by the gmm/decode MoE "
                    f"impls only (cfg {cfg.name!r} resolves to {impl!r})")
            params = quantize_expert_params(params, cfg, ed)
        rl = (opts.router_lookahead if router_lookahead is None
              else bool(router_lookahead))
        if rl and any(b.kind == "mamba" for b in cfg.pattern()):
            raise ValueError("router_lookahead carries the pre-FFN hidden "
                             "across layers; mamba blocks have none")
        if ed != opts.expert_dtype or rl != opts.router_lookahead:
            opts = replace(opts, expert_dtype=ed, router_lookahead=rl)
        self.expert_dtype = ed
        self.router_lookahead = rl

        self.runner = ModelRunner(cfg, params, mesh=mesh, opts=opts)
        self.plan_name = BASE_PLAN
        # pressure-adaptive plan degradation (DESIGN.md §10): an ordered
        # expensive -> cheap ladder of plan names (set after add_plan via
        # set_plan_ladder); under pool/queue pressure an admission moves a
        # non-priority request one rung down -- always at the prefill
        # boundary (the salt change makes the old cached prefix a miss,
        # so a resume recomputes under the new plan; a live slot's cache
        # is never mutated by a plan switch)
        self.plan_ladder: tuple = ()
        self.degrade_under_pressure = bool(degrade_under_pressure)
        self.degrade_watermark = float(degrade_watermark)
        self._kv_kw = dict(layout=cache_layout, page_size=page_size,
                           num_pages=num_pages,
                           prefix_cache=self.prefix_cache)
        # the KV pool is built from the runner's *split* serving config:
        # one cache entry per layer, identical across every plan/bucket
        # (what lets heterogeneous-plan slots share one pool)
        self.kv = KVCache(self.cfg, max_batch, max_len, **self._kv_kw)
        self.sched = Scheduler(max_batch, policy=scheduler,
                               clock=self.clock)

        # time-ordered arrival queue: requests submitted with a future
        # arrival_time sit here until the clock reaches them, then enter
        # the scheduler's WAITING set (open-loop mid-flight admission)
        self._pending: List = []        # heap of (arrival_time, seq, Request)
        self._pending_seq = 0
        self._pending_uids: set = set()

        self.slot_pos = np.full(max_batch, -1, np.int32)    # next write pos
        self.slot_last = np.zeros(max_batch, np.int32)      # last sampled tok
        self.slot_budget = np.zeros(max_batch, np.int32)
        self.slot_temp = np.zeros(max_batch, np.float32)
        self.slot_topk = np.zeros(max_batch, np.int32)      # 0 = no top-k cap
        #: one distinct-experts counter per MoE layer, in plan order
        self._routed_keys = tuple(
            f"experts_routed:l{i}"
            for i in range(len(self.runner.plan_ks[BASE_PLAN])))
        self.stats: Dict[str, float] = self._fresh_stats()
        self.runner.stats = self.stats      # the runner counts in here too

    def _fresh_stats(self) -> Dict[str, float]:
        # prefill_tokens counts each prompt position once (useful work);
        # positions re-prefilled when a preempted request resumes land in
        # recompute_tokens instead, so throughput() reflects useful tokens
        # prefix_hit_tokens counts positions served from cached pages
        # (never computed this admission); prefill_tokens keeps counting
        # only positions actually computed, so throughput() stays honest.
        # steps counts decode steps, chunk_steps chunked-prefill steps,
        # iterations calls of step().  host_s:<phase> is each phase's wall
        # time and host_cpu_s the pump's CPU time outside the waits
        # (serving/trace.py); experts_routed:l<i> sums, over decode steps,
        # the distinct experts MoE layer i routed the live slots to.
        # pool_copies counts the KV pools the runner copied for callers
        # that kept theirs (the engine hands its pool over: 0 in serving).
        out = {"prefill_tokens": 0, "decode_tokens": 0,
               "recompute_tokens": 0, "steps": 0, "preemptions": 0,
               "live_peak": 0, "prefix_hit_tokens": 0, "cow_copies": 0,
               "plan_degradations": 0, "mixed_plan_steps": 0,
               "chunk_steps": 0, "iterations": 0, "pool_copies": 0,
               CPU_KEY: 0.0}
        out.update({WALL_PREFIX + p: 0.0 for p in PHASES})
        out.update({k: 0 for k in self._routed_keys})
        return out

    # ------------------------------------------------------------------ #
    # Plans
    # ------------------------------------------------------------------ #
    @property
    def cfg(self) -> ModelConfig:
        return self.runner.cfg_for(self.plan_name)

    def add_plan(self, name: str, plan) -> ModelConfig:
        """Register a LExI plan; weights stay shared with the base config."""
        return self.runner.add_plan(name, plan)

    def set_plan_ladder(self, names: Sequence[str]) -> None:
        """Declare the degradation ladder, most expensive rung first.
        Every name must already be registered (``add_plan`` / "base")."""
        for n in names:
            if n not in self.runner.plans:
                raise ValueError(f"unknown plan {n!r} in ladder; "
                                 f"have {sorted(self.runner.plans)}")
        self.plan_ladder = tuple(names)

    def _under_pressure(self) -> bool:
        """KV-pool pressure (free pages below the watermark share) or
        compute pressure (more requests queued than slots free)."""
        if len(self.sched.waiting) > len(self.sched.free_slots()):
            return True
        if self.kv.layout == "paged":
            total = self.kv.num_pages - 1       # minus the trash page
            return total > 0 and (self.kv.free_pages()
                                  < self.degrade_watermark * total)
        return False

    def _degraded_rung(self, t: Tracked) -> str:
        """Plan to *try* admitting ``t`` under: its current rung, or one
        rung cheaper when the policy is on, the request is degradable
        (priority 0, on the ladder, not already at the bottom) and the
        system is under pressure.  At most one rung per admission attempt;
        the result is committed only if the allocation succeeds."""
        cur = t.served_plan
        if (not self.degrade_under_pressure or not self.plan_ladder
                or t.req.priority > 0 or cur not in self.plan_ladder):
            return cur
        i = self.plan_ladder.index(cur)
        if i + 1 >= len(self.plan_ladder) or not self._under_pressure():
            return cur
        return self.plan_ladder[i + 1]

    def _commit_plan(self, t: Tracked, served: str) -> None:
        """Record a successful admission's (possibly degraded) rung."""
        if served != t.served_plan:
            t.served_plan = served
            t.result.served_plan = served
            t.result.plan_degradations += 1
            self.stats["plan_degradations"] += 1

    def set_plan(self, name: str) -> None:
        """Switch the serving specialization (between workloads only).

        The weights are untouched; the KV pool is rebuilt empty only when
        the plan's layer grouping actually changes the cache pytree (the
        pool is drained between workloads, so reuse is safe otherwise)."""
        if name == self.plan_name:
            return
        if not self.sched.done() or self._pending:
            raise RuntimeError("cannot switch plans with requests in flight")
        old_cfg = self.cfg
        self.plan_name = name
        new_cfg = self.runner.cfg_for(name)
        if self._cache_shape(old_cfg) != self._cache_shape(new_cfg):
            self.kv = KVCache(new_cfg, self.max_batch, self.max_len,
                              **self._kv_kw)

    @staticmethod
    def _cache_shape(cfg: ModelConfig):
        """Cache-pytree fingerprint: group sizes + kinds (k doesn't matter)."""
        from repro.models.blocks import group_pattern
        return tuple((g.count, g.spec.kind)
                     for g in group_pattern(cfg.pattern()))

    # ------------------------------------------------------------------ #
    # Submission
    # ------------------------------------------------------------------ #
    def submit(self, req: Request, *,
               arrival_time: Optional[float] = None,
               detok: Union[bool, Callable] = False) -> None:
        """Enqueue a request for admission at ``arrival_time`` (clock
        units; ``None`` = now).  The open-loop entry point: requests may
        be submitted at any moment -- including while other requests are
        mid-prefill or mid-decode -- and enter the scheduler when the
        clock reaches their arrival time.  Validation (prompt length, KV
        capacity) happens at release, producing a rejected ``Result``
        rather than an exception.  ``detok`` is the workload-default
        incremental-detok mode, applied only when the request did not opt
        in itself; it is stamped on the engine-internal ``Tracked``
        record, never on the caller-owned ``Request`` (a request list
        reused across workloads must come back unchanged)."""
        if req.uid in self._pending_uids or req.uid in self.sched._uids:
            raise duplicate_uid_error(req.uid)
        t = self.clock.now() if arrival_time is None else float(arrival_time)
        heapq.heappush(self._pending, (t, self._pending_seq, req, detok))
        self._pending_seq += 1
        self._pending_uids.add(req.uid)

    def _release_arrivals(self) -> None:
        """Move every due arrival into the scheduler (arrival order)."""
        while self._pending and self._pending[0][0] <= self.clock.now():
            t_arr, _, req, detok = heapq.heappop(self._pending)
            self._pending_uids.discard(req.uid)
            self._submit(req, t_arrival=t_arr, detok_default=detok)

    def next_arrival(self) -> Optional[float]:
        """Earliest scheduled arrival still pending (None when empty) --
        what an external pump (the HTTP server) sleeps toward when
        nothing is runnable."""
        return self._pending[0][0] if self._pending else None

    def _submit(self, req: Request,
                t_arrival: Optional[float] = None,
                detok_default: Union[bool, Callable] = False) -> Tracked:
        t = self.sched.submit(req, t_submit=t_arrival)
        # resolve the plan once, at submission: a per-request plan wins,
        # otherwise the serve/engine default -- so serve(reqs, plan=) and
        # set_plan are exactly "stamp this plan on every request"
        t.plan = t.served_plan = (req.plan if req.plan is not None
                                  else self.plan_name)
        t.result.plan = t.result.served_plan = t.plan
        # the workload default applies only where the request itself did
        # not opt in, and lands on the Tracked record: the Request object
        # stays caller-owned state, not an engine scratchpad
        detok = req.detok if req.detok else detok_default
        if detok:
            t.detok = (IncrementalDetok(detok) if callable(detok)
                       else IncrementalDetok())
        limit = self.max_len - 1
        if t.prompt_len == 0:
            self.sched.reject(t, "rejected_empty_prompt")
        elif t.prompt_len > limit:
            if self.truncate_prompts:
                t.prompt = t.prompt[-limit:]
                t.result.truncated = True
                t.result.prompt_len = limit
            else:
                self.sched.reject(t, "rejected_prompt_too_long")
        if t.state != DONE and t.plan not in self.runner.plans:
            self.sched.reject(t, "rejected_unknown_plan")
        if (t.state != DONE
                and not self.kv.fits_ever(t.prompt_len
                                          + t.req.max_new_tokens)):
            self.sched.reject(t, "rejected_kv_capacity")
        return t

    # ------------------------------------------------------------------ #
    # Step phases
    # ------------------------------------------------------------------ #
    def _salt_for(self, served_plan: str):
        """Prefix-cache chain root key: everything (beyond the tokens)
        that changes what K/V a prefill writes.  The request's *served*
        LExI plan changes per-layer expert budgets -- hidden states and
        therefore K/V -- and the expert storage dtype changes numerics.
        Per-request salting is also what makes degradation safe: a
        degraded resume misses the old rung's cached prefix and
        recomputes everything under the new plan."""
        return (served_plan, self.expert_dtype)

    def _admission_headroom(self) -> int:
        """Free pages an admission must leave for slots already decoding,
        per the engine's admission policy (on-demand paging only).

        Admitting into the live slots' growth budget just preempts the
        newcomer right back out -- admit -> evict -> recompute churn that
        burns prefill work without finishing anyone -- so every policy
        except ``greedy`` holds some reserve back:

        * ``headroom`` (default): one page per decoding slot -- each may
          cross a page boundary within page_size steps (the anti-thrash
          heuristic DESIGN.md §6 introduced).
        * ``watermark``: a static reserve, ``admission_watermark`` of the
          pool -- independent of live state, so it neither adapts to a
          mostly-prefilling batch nor collapses when slots sit far from
          their next boundary.
        * ``lookahead``: the exact short-horizon need -- pages each
          decoding slot will claim within the next ``page_size`` steps,
          bounded by its remaining token budget.  Never more than
          ``headroom`` (<= one boundary per slot per page_size steps),
          so it admits at least as aggressively while still covering
          imminent growth.
        * ``greedy``: no reserve (the thrash baseline the others beat).
        """
        if self.admission == "greedy":
            return 0
        decoding = self.sched.in_state(DECODE)
        if self.admission == "headroom":
            return len(decoding)
        if self.admission == "watermark":
            total = self.kv.num_pages - 1       # minus the trash page
            return math.ceil(self.admission_watermark * total)
        need = 0                                # "lookahead"
        for t in decoding:
            have = int(self.slot_pos[t.slot]) + 1   # positions covered now
            horizon = min(self.kv.page_size,
                          max(int(self.slot_budget[t.slot]), 0))
            need += (self.kv.pages_needed(have + horizon)
                     - self.kv.pages_needed(have))
        return need

    def _admit(self) -> None:
        def can_allocate(slot: int, t: Tracked) -> bool:
            served = self._degraded_rung(t)
            if self.ondemand:
                # reserve only what this admission's prefill will write:
                # the prompt, plus generated-so-far minus the pending
                # token on resume.  Decode growth is allocate_append's
                # job; what must stay free for the already-decoding slots
                # is the admission policy's call (_admission_headroom).
                gen = t.result.tokens
                fill = (np.concatenate([t.prompt,
                                        np.asarray(gen[:-1], np.int32)])
                        if gen else t.prompt)
                n = len(fill)
                shared: List[int] = []
                hit = chain = 0
                if self.prefix_cache:
                    # a fresh request must compute >= 1 position (its
                    # logits come from the last prompt token); a resume
                    # may reuse everything -- the next token was sampled
                    # before eviction, so a full hit resumes straight to
                    # DECODE with zero recompute
                    cap = n if gen else n - 1
                    shared, hit, chain = self.kv.match_prefix(
                        self._salt_for(served), fill, cap)
                # gate against *private* need: pages the hit serves from
                # already-live (rc>=1) pages cost no pool capacity, while
                # an rc-0 LRU page costs one (pinning removes it from the
                # evictable set) and a COW boundary costs one private copy
                # -- which nets to pages_needed minus live non-boundary
                # hits.  fits_ever stays full-length (see KVCache).
                cow = 1 if hit % self.kv.page_size else 0
                cost = (self.kv.pages_needed(n)
                        - self.kv.live_count(shared[:len(shared) - cow]))
                headroom = self._admission_headroom()
                if self.kv.free_pages() < cost + headroom:
                    return False
                if not self.kv.allocate(slot, n, shared=shared,
                                        keep_below=hit):
                    return False
                if self.prefix_cache:
                    t.hit_len = hit
                    t.chain = chain
                    t.hashed_pages = hit // self.kv.page_size
                self._commit_plan(t, served)
                return True
            if not self.kv.allocate(slot,
                                    t.prompt_len + t.req.max_new_tokens):
                return False
            self._commit_plan(t, served)
            return True

        for t in self.sched.admit(can_allocate):
            self.slot_temp[t.slot] = t.req.temperature
            # a top-k cap is meaningless at temperature 0 (greedy already
            # takes the k=1 maximizer); recording it anyway would force
            # the full-vocab sort path in _topks() for no output change
            self.slot_topk[t.slot] = (t.req.top_k
                                      if t.req.temperature > 0 else 0)
            gen = t.result.tokens
            if gen:     # resume: re-prefill prompt + all but the pending tok
                t.fill = np.concatenate(
                    [t.prompt, np.asarray(gen[:-1], np.int32)])
            else:
                t.fill = t.prompt
            self.slot_budget[t.slot] = t.req.max_new_tokens - len(gen)
            self.slot_pos[t.slot] = -1
            if t.hit_len:
                # mapped-in pages cover [0, hit_len): chunked prefill
                # starts at the first uncached position
                self.stats["prefix_hit_tokens"] += t.hit_len
                t.result.prefix_hit_tokens += t.hit_len
                if t.hit_len % self.kv.page_size:
                    self.stats["cow_copies"] += 1
                    t.result.cow_copies += 1
                t.consumed = t.hit_len
                if t.consumed == t.fill_len:
                    # resume with the whole fill still cached: the third,
                    # nearly-free resume mode -- no recompute at all
                    assert t.resuming
                    t.state = DECODE
                    self.slot_pos[t.slot] = t.fill_len
                    self.slot_last[t.slot] = t.result.tokens[-1]
            if not self.chunked:
                self._whole_prefill(t)

    def _topks(self):
        """Per-slot top-k caps for sampling, or None when no slot uses one
        (the common all-greedy case skips the full-vocab sort entirely)."""
        return jnp.asarray(self.slot_topk) if self.slot_topk.any() else None

    def _eos_of(self, t: Tracked) -> Optional[int]:
        """Effective stop token: per-request override, engine default
        otherwise -- checked per slot, so requests with different stop
        tokens batch together."""
        return t.req.eos_id if t.req.eos_id is not None else self.eos_id

    def _first_token(self, t: Tracked, tok: int) -> None:
        """Account the prefill-sampled token; it may already terminate."""
        if t.req.max_new_tokens <= 0:
            # prompt-only request: nothing was asked for, so nothing is
            # recorded -- it finishes with zero decode tokens and
            # contributes no latency samples (percentiles stay NaN-free)
            self._finish(t, "length")
            return
        self.sched.record_token(t, tok)
        self.slot_budget[t.slot] -= 1
        eos = self._eos_of(t)
        done_eos = eos is not None and tok == eos
        if done_eos or self.slot_budget[t.slot] <= 0:
            self._finish(t, "eos" if done_eos else "length")
        else:
            t.state = DECODE
            self.slot_pos[t.slot] = t.prompt_len
            self.slot_last[t.slot] = tok

    def _finish(self, t: Tracked, reason: str) -> None:
        slot = t.slot
        self.sched.finish(t, reason)
        self.kv.release(slot)
        self.slot_pos[slot] = -1
        self.slot_topk[slot] = 0    # lingering caps would keep _topks() hot
        k = f"plan_requests:{t.served_plan}"
        self.stats[k] = self.stats.get(k, 0) + 1

    def _plan_batch(self, live: List[Tracked]):
        """-> (plan, bucket, k_budgets) for one batched model step.

        All live slots on one plan: that plan's own static-k graph, no
        budgets (zero overhead vs the single-plan engine, bitwise the
        same numerics).  Mixed plans: the bucketed-k graph for the
        batch's per-layer max k (pow2 roundup), with each slot's true
        per-layer budget -- surplus routed slots are zero-weighted in
        route(), so every row is bitwise what its own plan's graph
        computes (DESIGN.md §10)."""
        names = {t.served_plan for t in live}
        if len(names) == 1:
            return names.pop(), None, None
        ks = self.runner.plan_ks
        n_moe = len(ks[BASE_PLAN])
        maxk = tuple(max(ks[t.served_plan][l] for t in live)
                     for l in range(n_moe))
        bucket = self.runner.bucket_for(maxk)
        budgets = np.tile(np.asarray(bucket, np.int32), (self.max_batch, 1))
        for t in live:
            budgets[t.slot] = ks[t.served_plan]
        self.stats["mixed_plan_steps"] += 1
        return BASE_PLAN, bucket, budgets

    def _whole_prefill(self, t: Tracked) -> None:
        """Legacy [1, padded_len] prefill + slot scatter (mamba fallback)."""
        plen = t.prompt_len
        pad = min(-(-plen // self.prefill_pad) * self.prefill_pad,
                  self.max_len)
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, -plen:] = t.prompt                        # right-aligned
        positions = np.full((1, pad), -1, np.int32)
        positions[0, -plen:] = np.arange(plen)
        one_cache = models.init_caches(self.cfg, 1, self.max_len)
        logits, one_cache = self.runner.whole_prefill(
            jnp.asarray(tokens), jnp.asarray(positions), one_cache,
            plan=t.served_plan)
        self.kv.scatter_slot(one_cache, t.slot)
        self.stats["prefill_tokens"] += plen
        t.consumed = plen
        self.key, sub = jax.random.split(self.key)
        nxt = np.asarray(sample_per_slot(
            logits, sub, jnp.asarray([t.req.temperature], jnp.float32),
            jnp.asarray([t.req.top_k], jnp.int32)
            if t.req.top_k and t.req.temperature > 0 else None))
        self._first_token(t, int(nxt[0]))

    def _seq_tokens(self, t: Tracked, a: int, b: int) -> np.ndarray:
        """Token content at positions [a, b): the prompt, then generated
        tokens (position i >= prompt_len holds ``result.tokens[i - L]``
        -- decode writes each sampled token at the position it occupies)."""
        lo = t.prompt[a:b]
        if b <= t.prompt_len:
            return lo
        gen = np.asarray(t.result.tokens[max(a - t.prompt_len, 0):
                                         b - t.prompt_len], np.int32)
        return np.concatenate([lo, gen]) if len(lo) else gen

    def _register_pages(self, t: Tracked, written: int) -> None:
        """Index every newly *full* page of ``t``'s slot (content below
        ``written`` is final: chunk prefill / decode writes committed).
        First-wins dedup in the index keeps duplicates private; the chain
        id advances either way so the next page keys correctly."""
        if not self.prefix_cache:
            return
        p = self.kv.page_size
        while (t.hashed_pages + 1) * p <= written:
            j = t.hashed_pages
            page = self.kv.slot_pages(t.slot)[j]
            t.chain = self.kv.register_page(
                t.chain, self._seq_tokens(t, j * p, (j + 1) * p), page)
            t.hashed_pages += 1

    def _chunk_prefill_step(self, prefilling: List[Tracked]) -> None:
        """Advance every prefilling slot by one fixed-width chunk.

        Fresh and resuming (post-preemption) requests ride the same
        ``(plan, "chunk", C)`` graph -- resume is not a new graph family.
        A resuming slot's chunks count as recompute, and finishing its
        fill transitions straight to DECODE with the token sampled before
        eviction: no re-sampling, no re-fired streaming callbacks.

        With prefix caching a slot's ``consumed`` starts at ``hit_len``
        (mapped-in pages serve the positions below), so the chunk's
        positions/tokens start at the first uncached position with no
        graph change -- positions are explicit arrays already.
        """
        st = self.stats
        with phase(st, "engine.chunk.prepare"):
            c = self.prefill_chunk
            tokens = np.zeros((self.max_batch, c), np.int32)
            positions = np.full((self.max_batch, c), -1, np.int32)
            last_idx = np.zeros(self.max_batch, np.int32)
            sampling: List[Tracked] = []
            n_tok = 0
            for t in prefilling:
                n = min(c, t.fill_len - t.consumed)
                n_tok += n
                tokens[t.slot, :n] = t.fill[t.consumed:t.consumed + n]
                positions[t.slot, :n] = np.arange(t.consumed, t.consumed + n)
                self.kv.assert_private(t.slot, t.consumed, t.consumed + n)
                t.consumed += n
                if t.resuming:
                    st["recompute_tokens"] += n
                    t.result.recompute_tokens += n
                else:
                    # a victim evicted mid-prefill re-runs positions
                    # already charged as useful work: only the advance
                    # past its prefill high-water mark counts as fresh
                    fresh = min(n, max(0, t.consumed - t.prefill_done))
                    st["prefill_tokens"] += fresh
                    st["recompute_tokens"] += n - fresh
                    t.result.recompute_tokens += n - fresh
                    t.prefill_done = max(t.prefill_done, t.consumed)
                if t.consumed == t.fill_len:
                    if t.resuming:
                        t.state = DECODE
                        self.slot_pos[t.slot] = t.fill_len
                        self.slot_last[t.slot] = t.result.tokens[-1]
                    else:
                        last_idx[t.slot] = n - 1
                        sampling.append(t)
            plan, bucket, budgets = self._plan_batch(prefilling)
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(last_idx), self.kv.caches,
                    self.kv.block_tables())
        with phase(st, "engine.chunk.dispatch", step=int(st["chunk_steps"]),
                   plan=_plan_label(plan, bucket), tokens=n_tok):
            logits, self.kv.caches = self.runner.chunk_prefill(
                *args, plan=plan, bucket=bucket, k_budgets=budgets,
                donate=True)
            del args        # the donated pool: deleted by the call
            st["chunk_steps"] += 1
        with phase(st, "engine.chunk.sample"):
            for t in prefilling:    # chunk writes are committed: index them
                self._register_pages(t, t.consumed)
            if sampling:
                self.key, sub = jax.random.split(self.key)
                nxt = sample_per_slot(logits, sub,
                                      jnp.asarray(self.slot_temp),
                                      self._topks())
            else:
                # freeing device arrays can hand the GIL to the connection
                # threads: do it in a phase, not on the way out
                del logits
        if sampling:
            with phase(st, "engine.chunk.wait"):
                nxt = np.asarray(nxt)
            with phase(st, "engine.chunk.commit"):
                del logits, sub
                for t in sampling:
                    self._first_token(t, int(nxt[t.slot]))

    def _preempt(self, t: Tracked) -> None:
        """Evict a live request: pages back to the pool, request re-queued
        PREEMPTED (its generated tokens are kept for the resume prefill)."""
        slot = t.slot
        self.sched.preempt(t)
        self.kv.release(slot)
        self.slot_pos[slot] = -1
        self.slot_budget[slot] = 0
        self.slot_temp[slot] = 0.0
        self.slot_topk[slot] = 0
        self.stats["preemptions"] += 1

    def _grow_or_preempt(self, decoding: List[Tracked]) -> List[Tracked]:
        """On-demand allocation before the decode write: every decoding
        slot gets the page its next position needs; a pool shortfall
        preempts victims last-admitted-first until the allocation fits.

        Growing earliest-admitted-first while evicting latest-first means
        a victim is never a slot already grown this step, and the earliest
        live request is never evicted by a later one -- with ``fits_ever``
        guaranteeing any single admitted request fits the whole pool, that
        request always completes, so repeated preemption cannot livelock.
        """
        for t in sorted(decoding, key=lambda t: t.admit_seq):
            if t.state != DECODE:           # evicted as a victim below
                continue
            while not self.kv.allocate_append(t.slot,
                                              int(self.slot_pos[t.slot]) + 1):
                live = [v for v in self.sched.slots if v is not None]
                victim = max(live, key=lambda v: v.admit_seq)
                self._preempt(victim)
                if victim is t:
                    break
        return self.sched.in_state(DECODE)

    def _decode_step(self, decoding: List[Tracked]) -> None:
        st = self.stats
        with phase(st, "engine.decode.prepare"):
            if self.ondemand:
                decoding = self._grow_or_preempt(decoding)
            if decoding:
                tokens = np.zeros(self.max_batch, np.int32)
                pos = np.full(self.max_batch, -1, np.int32)
                for t in decoding:
                    tokens[t.slot] = self.slot_last[t.slot]
                    pos[t.slot] = self.slot_pos[t.slot]
                    # decode never writes into a shared (rc>1) page: the
                    # write position is past the shared prefix by
                    # construction (COW copied the boundary page at
                    # admission)
                    self.kv.assert_private(t.slot, int(pos[t.slot]),
                                           int(pos[t.slot]) + 1)
                kernel_blocks = (self.kv.live_blocks(pos)
                                 if self.use_kernel
                                 and self.kv.layout == "paged" else None)
                plan, bucket, budgets = self._plan_batch(decoding)
                kv_tokens = int(np.sum(pos[pos >= 0] + 1))
                args = (jnp.asarray(tokens), jnp.asarray(pos),
                        self.kv.caches, self.kv.block_tables())
        if not decoding:
            return
        with phase(st, "engine.decode.dispatch", step=int(st["steps"]),
                   plan=_plan_label(plan, bucket), live=len(decoding),
                   kv_tokens=kv_tokens):
            logits, self.kv.caches = self.runner.decode(
                *args, plan=plan, use_kernel=self.use_kernel,
                kernel_blocks=kernel_blocks, moe_decode=self.use_moe_decode,
                bucket=bucket, k_budgets=budgets, donate=True)
            del args        # the donated pool: deleted by the call
        with phase(st, "engine.decode.sample"):
            self.key, sub = jax.random.split(self.key)
            nxt = sample_per_slot(logits, sub, jnp.asarray(self.slot_temp),
                                  self._topks())
        with phase(st, "engine.decode.wait"):
            # one fetch: the step's tokens and its routed-experts count
            nxt, routed = jax.device_get((nxt, self.runner.routed))
        with phase(st, "engine.decode.commit"):
            # freeing device arrays can hand the GIL to the connection
            # threads: do it here, not on the way out of the method
            del logits, sub
            st["steps"] += 1
            for key, n in zip(self._routed_keys, routed):
                st[key] += int(n)
            for t in decoding:
                self.slot_pos[t.slot] += 1
                tok = int(nxt[t.slot])
                self.sched.record_token(t, tok)
                self.slot_last[t.slot] = tok
                self.slot_budget[t.slot] -= 1
                st["decode_tokens"] += 1
                k = f"plan_decode_tokens:{t.served_plan}"
                st[k] = st.get(k, 0) + 1
                # register before any finish: a finishing request's pages
                # park in the LRU (content intact) instead of the free
                # list, so its prefix stays reusable after release
                self._register_pages(t, int(self.slot_pos[t.slot]))
                eos = self._eos_of(t)
                done_eos = eos is not None and tok == eos
                done_len = (self.slot_budget[t.slot] <= 0
                            or self.slot_pos[t.slot] >= self.max_len - 1)
                if done_eos or done_len:
                    self._finish(t, "eos" if done_eos else "length")

    def _abort(self, reason: str) -> None:
        """Drain every live, queued, and not-yet-arrived request so a
        failed serve()/drain() cannot wedge the engine: pages go back to
        the pool, slots clear, and the finished records release their uid
        claims at the next serve()."""
        for t in [x for x in self.sched.slots if x is not None]:
            self._finish(t, reason)
        for t in list(self.sched.waiting):
            self.sched.reject(t, reason)
        while self._pending:    # future arrivals reject without admission
            _, _, req, _ = heapq.heappop(self._pending)
            self._pending_uids.discard(req.uid)
            self.sched.reject(self.sched.submit(req), reason)

    def _step(self) -> None:
        with phase(self.stats, "engine.admit"):
            self._release_arrivals()
            self._admit()
            live = sum(t is not None for t in self.sched.slots)
            self.stats["live_peak"] = max(self.stats["live_peak"], live)
        prefilling = self.sched.in_state(PREFILL)
        if prefilling:
            self._chunk_prefill_step(prefilling)
        decoding = self.sched.in_state(DECODE)
        if decoding:
            self._decode_step(decoding)

    # ------------------------------------------------------------------ #
    # Public API
    # ------------------------------------------------------------------ #
    def idle(self) -> bool:
        """Nothing live, queued, or scheduled to arrive."""
        return not self._pending and self.sched.done()

    def reset_stats(self) -> None:
        """Start a fresh workload: zero the throughput counters and drop
        the previous workload's finished records (releasing their uid
        claims).  Refused while requests are in flight -- counters and
        records mid-workload would be corrupted, not reset."""
        if not self.idle():
            raise RuntimeError("cannot reset stats with requests in flight")
        self.stats = self.runner.stats = self._fresh_stats()
        self.sched.clear_finished()

    def pop_finished(self) -> List[Result]:
        """Incrementally retire finished records: return their results
        and release the records and uid claims.  The open-loop lifecycle
        seam ``reset_stats``/``clear_finished`` cannot provide: a
        long-lived server pumps ``step()`` and is *never* idle, so
        without per-result retirement ``sched.finished`` grows without
        bound and every uid stays claimed forever.  Works mid-flight;
        counters are untouched (only records are released)."""
        return self.sched.pop_finished()

    def cancel(self, uid, *, reason: str = "cancelled") -> bool:
        """Abort one request wherever it currently lives: not yet
        arrived (removed from the arrival heap), queued
        (WAITING/PREEMPTED, rejected), or live in a slot (finished, KV
        pages released).  Either way the request retires as a finished
        record with ``finished_reason=reason`` -- retrieved (and its uid
        claim released) by the next ``pop_finished``.  Returns False
        when the uid is unknown or already finished.  The HTTP front end
        maps a client disconnect here, so an abandoned stream cannot
        hold pages, a slot, or a uid claim."""
        for i, (t_arr, _, req, _) in enumerate(self._pending):
            if req.uid == uid:
                del self._pending[i]
                heapq.heapify(self._pending)
                self._pending_uids.discard(uid)
                self.sched.reject(self.sched.submit(req, t_submit=t_arr),
                                  reason)
                return True
        for t in list(self.sched.waiting):
            if t.req.uid == uid:
                self.sched.reject(t, reason)
                return True
        for t in self.sched.slots:
            if t is not None and t.req.uid == uid:
                self._finish(t, reason)
                return True
        return False

    def step(self) -> List[Result]:
        """One engine iteration: release due arrivals, admit, advance one
        chunked-prefill step and one decode step, tick the clock.
        Returns the requests that *completed this step* (possibly empty)
        -- per-request completion never waits for the rest of the batch.
        Non-blocking: an idle step (waiting on a future arrival) does no
        work and returns immediately."""
        n0 = len(self.sched.finished)
        with phase(None, "engine.step", step=int(self.stats["iterations"])):
            self._step()
            self.clock.on_step()
            self.stats["iterations"] += 1
        return [t.result for t in self.sched.finished[n0:]]

    def drain(self, *, max_steps: Optional[int] = None) -> List[Result]:
        """Step until the system is empty (live slots, waiting queue, and
        arrival queue all drained); returns every request completed during
        the drain.  While nothing is runnable and the next arrival is in
        the future, the clock idles toward it (a wall clock sleeps, a
        virtual clock jumps -- idle simulated time is free).  ``max_steps``
        bounds the engine-step loop (livelock guard): exceeding it aborts
        every in-flight request and raises RuntimeError."""
        out: List[Result] = []
        n_steps = 0
        while not self.idle():
            if max_steps is not None and n_steps >= max_steps:
                queued, live = (len(self.sched.waiting),
                                sum(t is not None for t in self.sched.slots))
                self._abort("aborted_max_steps")    # engine stays reusable
                raise RuntimeError(
                    f"drain() exceeded max_steps={max_steps}: "
                    f"{queued} queued, {live} live "
                    f"({self.stats['preemptions']} preemptions so far)")
            if (self._pending and self.sched.done()
                    and self._pending[0][0] > self.clock.now()):
                self.clock.sleep_until(self._pending[0][0])
            out.extend(self.step())
            n_steps += 1
        return out

    def serve(self, requests: Sequence[Request], *,
              plan: Optional[str] = None,
              detok=False,
              max_steps: Optional[int] = None,
              arrival_times: Optional[Sequence[float]] = None) -> List[Result]:
        """Run a full workload with continuous batching; returns all results.

        A thin wrapper over ``submit`` + ``drain``: every request is
        submitted up front -- at t=now (the closed-loop default, identical
        to the historical batch call) or at ``now + arrival_times[i]``
        (open-loop: per-request arrival offsets in clock units, e.g. a
        Poisson process for the offered-load bench) -- and the engine
        steps until all have completed.

        Throughput counters and latency percentiles are per-serve (reset at
        entry).  ``plan=`` sets this serve's *default* plan -- exactly
        equivalent to stamping it on every request whose ``Request.plan``
        is None; requests carrying their own plan mix freely in the batch
        (DESIGN.md §10).  Omitting it serves the base config (a previous
        serve's plan does not stick).  ``detok=`` turns on incremental
        detokenized streaming for every request that did not opt in
        itself (True = default synthetic detokenizer, or an ``ids ->
        text`` callable).  ``max_steps`` bounds the engine-step loop (a
        livelock guard for stress harnesses): exceeding it raises
        RuntimeError.
        """
        self.set_plan(plan if plan is not None else BASE_PLAN)
        # refuse duplicate uids before anything is submitted: a mid-batch
        # refusal would leave the earlier requests queued (and their uids
        # claimed) with no way to drain them -- the scheduler-level guard
        # stays as defense for direct submit() users
        uids = [r.uid for r in requests]
        if len(set(uids)) != len(uids):
            seen = set()
            dup = next(u for u in uids if u in seen or seen.add(u))
            raise duplicate_uid_error(dup)
        if arrival_times is not None and len(arrival_times) != len(requests):
            raise ValueError(f"{len(arrival_times)} arrival_times for "
                             f"{len(requests)} requests")
        self.reset_stats()      # records (and uid claims) are per-workload:
        # a long-lived engine must not accumulate them
        t0 = self.clock.now()
        for i, r in enumerate(requests):
            off = arrival_times[i] if arrival_times is not None else 0.0
            # detok rides as the workload default, stamped on the Tracked
            # at release -- never written back onto the caller's Request
            self.submit(r, arrival_time=t0 + off, detok=detok)
        self.drain(max_steps=max_steps)
        self.stats["wall_s"] = max(self.clock.now() - t0, 0.0)
        # share of prefill-source positions served from cached pages (0.0
        # when nothing was prefilled at all, so the stat is always finite)
        hit = self.stats["prefix_hit_tokens"]
        denom = (hit + self.stats["prefill_tokens"]
                 + self.stats["recompute_tokens"])
        self.stats["prefix_hit_rate"] = hit / denom if denom else 0.0
        self.stats.update(self.sched.percentiles())
        return self.sched.results()

    def plan_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-plan view of the last serve's counters: plan name ->
        {"plan_requests": n, "plan_decode_tokens": n} (stats themselves
        stay flat scalar keys ``plan_requests:<name>`` etc)."""
        out: Dict[str, Dict[str, float]] = {}
        for k, v in self.stats.items():
            if k.startswith(("plan_requests:", "plan_decode_tokens:")):
                stat, name = k.split(":", 1)
                out.setdefault(name, {})[stat] = v
        return out

    def throughput(self) -> float:
        """Useful tokens (prompt + generated) per second over the last
        serve().  Positions re-prefilled by preemption recovery are
        accounted separately (``stats["recompute_tokens"]``) -- recompute
        is overhead, not throughput.  Zero wall time (an instant
        virtual-clock workload, or a server that never ran ``serve()``)
        reports 0.0, never NaN: the value flows straight into report
        lines, JSON cells, and ``/v1/stats``, all of which must stay
        finite."""
        wall = self.stats.get("wall_s", 0.0)
        tok = self.stats["prefill_tokens"] + self.stats["decode_tokens"]
        return tok / wall if wall > 0 else 0.0
