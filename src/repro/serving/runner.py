"""Model runner: weights + a table of compiled step specializations.

The runner owns the parameters and every jitted graph the engine steps
through.  Graphs are cached in a specialization table keyed by
``(plan, kind, width, ...)``:

* ``(plan, "decode", B, use_kernel, n_blocks, moe_decode, expert_dtype)``
  -- one-token step over all B slots.  ``use_kernel`` switches paged
  decode between the gather oracle and the block-table-native
  flash-decode kernel; ``n_blocks`` is the kernel's static live-page walk
  bound (a power-of-two bucket from ``KVCache.live_blocks``), so a
  growing context steps through at most O(log n_blk) graphs while short
  contexts never pay full-table traffic; ``moe_decode`` routes
  decode-shaped MoE dispatch through the fused routed-expert path instead
  of the sort-based gmm plan;
* ``(plan, "chunk", C, expert_dtype)`` -- fixed-width ``[B, C]``
  chunked-prefill step: every prompt, whatever its length, runs through
  this single graph (no more jit-per-padded-length).  Preemption resume
  rides this same graph -- re-prefilling a victim's prompt +
  generated-so-far is just a longer fill, so recompute adds no new graph
  family.  Prefix-cache entry offsets ride it too: positions are explicit
  ``[B, C]`` arrays, so a fill starting at the first uncached position
  (engine ``consumed = hit_len``) is just different position values, not
  a new graph -- the kernel/oracle attention paths need no changes;
* ``(plan, "prefill", L, expert_dtype)`` -- legacy whole-prompt ``[1, L]``
  graph for stacks chunked prefill cannot serve (mamba state carry).

``expert_dtype`` (appended last so older key-indexing callers keep
working) is the expert-tile storage dtype from ``opts``: quantized and
bf16 engines must never share a compiled graph, because the quantized
graphs bake in the int8/scale-row parameter layout.

The graphs are jitted named functions -- ``decode_step``, ``chunk_step``,
``prefill_step`` -- so a device trace names each program
(``jit_decode_step``).  The decode program returns one more output than
``decode`` does: the distinct experts each MoE layer routed the live
slots to, which the runner keeps in ``routed`` for the engine.

Who owns the KV pool
--------------------
The decode and chunk programs donate their cache argument: XLA writes the
step's tokens into the pool's own pages instead of copying the whole pool
into a new buffer first.  The caller says whether it gives the pool up.
The engine, the pool's only owner, passes ``donate=True`` and replaces
its pool with the returned one at once.  Any other caller (tests, a
benchmark's warm-up) keeps the default: the runner copies the pool, hands
the copy to the same donating program -- so what a warm-up compiles is
what the engine runs -- and leaves the caller's arrays live.  Each such
copy is counted in ``stats["pool_copies"]``; once serving, it stays 0.

Per-request plans (DESIGN.md §10)
---------------------------------
Every serving graph runs a **per-layer split** of the config's pattern:
each layer gets a unique ``BlockSpec.split_id``, so the KV-cache pytree
has exactly one entry per layer and is *independent* of the per-layer
top-k.  That is what lets one engine-held cache serve any mix of plans --
a plan only changes each layer's static ``moe_top_k``, never the cache
structure.  All plans share one split-regrouped parameter view (expert
weights do not depend on k; loaded exactly once).

A batch whose live slots all share one plan steps through that plan's
``(plan, ...)`` graphs exactly as before.  A *mixed* batch steps through a
**bucketed-k** graph instead, keyed by
``(("bucket", k_0, ..., k_{n-1}), kind, ...)`` where ``k_l`` is the
power-of-two roundup of the batch's per-layer max plan k (clamped to
``num_experts``).  Slots budgeted fewer experts than the bucket pass a
dynamic ``k_budgets [B, n_moe]`` argument whose surplus routed slots get
weight exactly 0.0 in ``route`` -- bitwise the same outputs as the slot's
own static-k graph, so bucket graphs are numerics-preserving and the
graph count stays O(log(E)^n_distinct) instead of one per plan combination.
"""

from __future__ import annotations

from dataclasses import replace as dc_replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import models
from repro.configs.base import ModelConfig
from repro.models import blocks as blocks_mod
from repro.models.opts import DEFAULT_OPTS, ModelOpts

BASE_PLAN = "base"


def split_pattern(cfg: ModelConfig) -> Tuple:
    """Per-layer split of ``cfg``'s resolved pattern (unique split_id each)."""
    return tuple(dc_replace(s, split_id=i)
                 for i, s in enumerate(cfg.pattern()))


def _split_cfg(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` with its (plan-resolved) pattern pinned to per-layer groups."""
    return cfg.with_(block_pattern=split_pattern(cfg), lexi_plan=None)


def bucket_k(k: int, num_experts: int) -> int:
    """Power-of-two roundup of ``k``, clamped to the expert count."""
    b = 1
    while b < k:
        b *= 2
    return min(b, num_experts)


def init_serving_params(key, cfg: ModelConfig):
    """Random parameters made directly in the runner's per-layer split layout.

    One jitted program writes each weight once, in its storage dtype, so
    the device holds neither a grouped copy beside the split one (what
    ``ModelRunner`` would otherwise regroup into) nor f32 init
    temporaries -- what lets a published-width model fill most of a chip.
    """
    split = _split_cfg(cfg)
    return jax.jit(lambda k: models.init_params(k, split))(key)


def _is_split(stack, serve_cfg: ModelConfig) -> bool:
    return (len(stack["groups"])
            == len(blocks_mod.group_pattern(serve_cfg.pattern())))


class ModelRunner:
    def __init__(self, cfg: ModelConfig, params, *, mesh=None,
                 opts: ModelOpts = DEFAULT_OPTS):
        self.mesh = mesh
        self.opts = opts
        self.base_cfg = cfg
        serve_cfg = _split_cfg(cfg)
        serve_params = params
        # params already in the split layout (``init_serving_params``) are
        # served as given: regrouping slices every layer into new buffers
        if "stack" in params and not _is_split(params["stack"], serve_cfg):
            serve_params = dict(params)
            serve_params["stack"] = blocks_mod.regroup_stack(
                params["stack"], cfg.pattern(), serve_cfg.pattern())
        #: the single split-regrouped parameter view every plan shares
        self.params = serve_params
        #: plan name -> split serving config; "base" is the config as given
        self.plans: Dict[str, ModelConfig] = {BASE_PLAN: serve_cfg}
        #: plan name -> per-MoE-layer top-k tuple (budget source for mixing)
        self.plan_ks: Dict[str, Tuple[int, ...]] = {
            BASE_PLAN: self._moe_ks(serve_cfg)}
        self._bucket_cfgs: Dict[Tuple[int, ...], ModelConfig] = {}
        self._jit: Dict[Tuple, Any] = {}
        #: the last decode step's ``[n_moe]`` i32 device array: distinct
        #: experts each MoE layer routed the live slots to (the engine
        #: fetches it with the step's sampled tokens)
        self.routed = None
        #: counters the runner keeps; the engine hands in its own stats
        #: dict so they show beside its counters
        self.stats: Dict[str, float] = {"pool_copies": 0}

    @staticmethod
    def _moe_ks(cfg: ModelConfig) -> Tuple[int, ...]:
        return tuple(s.moe_top_k for s in cfg.pattern()
                     if s.kind == "attn_moe")

    # ------------------------------------------------------------------ #
    # Plans
    # ------------------------------------------------------------------ #
    def add_plan(self, name: str, plan) -> ModelConfig:
        """Register a LExI plan under ``name``; returns its config."""
        if name == BASE_PLAN:
            raise ValueError(f"{BASE_PLAN!r} names the unplanned base "
                             "specialization; register plans under another "
                             "name")
        ks = tuple(int(k) for k in getattr(plan, "plan", plan))
        plan_cfg = self.base_cfg.with_lexi_plan(ks)
        plan_cfg.pattern()                     # validate lengths / ranges
        self.plans[name] = _split_cfg(plan_cfg)
        self.plan_ks[name] = ks
        return plan_cfg

    def cfg_for(self, plan: str = BASE_PLAN) -> ModelConfig:
        return self.plans[plan]

    def bucket_for(self, ks: Tuple[int, ...]) -> Tuple[int, ...]:
        """Per-layer max-k vector -> its power-of-two bucket vector."""
        e = self.base_cfg.num_experts
        return tuple(bucket_k(int(k), e) for k in ks)

    def _cfg_for_bucket(self, bucket: Tuple[int, ...]) -> ModelConfig:
        if bucket not in self._bucket_cfgs:
            base = self.plans[BASE_PLAN]
            pat, mi = [], 0
            for s in base.pattern():
                if s.kind == "attn_moe":
                    pat.append(dc_replace(s, moe_top_k=int(bucket[mi])))
                    mi += 1
                else:
                    pat.append(s)
            if mi != len(bucket):
                raise ValueError(f"bucket length {len(bucket)} != "
                                 f"#MoE layers {mi}")
            self._bucket_cfgs[bucket] = base.with_(block_pattern=tuple(pat))
        return self._bucket_cfgs[bucket]

    def _resolve(self, plan: str, bucket):
        """-> (key head, serving cfg) for a homogeneous plan or a bucket."""
        if bucket is None:
            return plan, self.plans[plan]
        bucket = tuple(int(b) for b in bucket)
        return ("bucket", *bucket), self._cfg_for_bucket(bucket)

    def compiled_specializations(self) -> Tuple[Tuple, ...]:
        """Keys of every graph compiled so far (introspection / tests)."""
        return tuple(sorted(self._jit, key=str))

    def _own(self, caches, donate: bool):
        """The pool a donating step may consume: ``caches`` itself when the
        caller gives it up, else a copy (counted), so the caller's arrays
        stay live."""
        if donate:
            return caches
        self.stats["pool_copies"] += 1
        return jax.tree.map(jnp.copy, caches)

    # ------------------------------------------------------------------ #
    # Steps
    # ------------------------------------------------------------------ #
    def decode(self, tokens, pos, caches, block_tables=None, *,
               plan: str = BASE_PLAN, use_kernel: Optional[bool] = None,
               kernel_blocks: Optional[int] = None,
               moe_decode: Optional[bool] = None,
               bucket: Optional[Tuple[int, ...]] = None, k_budgets=None,
               donate: bool = False):
        """One decode step over all slots -> (logits [B,V], caches).

        ``use_kernel`` (None -> ``opts.use_paged_kernel``) selects the
        block-table-native paged flash-decode; ``kernel_blocks`` is its
        static walk bound.  ``moe_decode`` (None ->
        ``opts.use_moe_decode_kernel``) selects the fused routed-expert
        MoE path for the step.  All three join the specialization key.

        ``bucket`` (per-MoE-layer static k vector) + ``k_budgets``
        ([B, n_moe] i32) select a mixed-plan bucket graph instead of
        ``plan``'s graph; surplus routed slots are zero-weighted exactly.

        The step program also counts the distinct experts each MoE layer
        routed the live slots to, within their budgets; the count stays on
        the device in ``self.routed`` until the engine fetches it.

        ``donate=True`` gives ``caches`` up: the step updates its pages in
        place and the caller's arrays are deleted.  Otherwise the step runs
        on a copy and ``caches`` stays live.
        """
        fn, args = self._decode_call(
            tokens, pos, self._own(caches, donate), block_tables, plan=plan,
            use_kernel=use_kernel, kernel_blocks=kernel_blocks,
            moe_decode=moe_decode, bucket=bucket, k_budgets=k_budgets)
        logits, caches, self.routed = fn(*args)
        return logits, caches

    def _decode_call(self, tokens, pos, caches, block_tables=None, *,
                     plan: str = BASE_PLAN, use_kernel=None,
                     kernel_blocks=None, moe_decode=None, bucket=None,
                     k_budgets=None):
        head, cfg = self._resolve(plan, bucket)
        uk = self.opts.use_paged_kernel if use_kernel is None else bool(use_kernel)
        md = (self.opts.use_moe_decode_kernel if moe_decode is None
              else bool(moe_decode))
        if block_tables is None:            # contiguous layout: gather-free
            uk, kernel_blocks = False, None
        key = (head, "decode", int(tokens.shape[0]), uk, kernel_blocks, md,
               self.opts.expert_dtype)
        if key not in self._jit:
            opts = dc_replace(self.opts, use_paged_kernel=uk,
                              use_moe_decode_kernel=md)
            kb = kernel_blocks

            def decode_step(p, t, po, c, bt, kbud):
                return models.decode_fn(
                    p, cfg, t, po, c, block_tables=bt, mesh=self.mesh,
                    opts=opts, kernel_blocks=kb, k_budgets=kbud,
                    count_routed=True)
            # a named function: the device trace names the program after
            # it; the pool (argument 3) is updated in place
            self._jit[key] = jax.jit(decode_step, donate_argnums=3)
        if bucket is not None:
            k_budgets = jnp.asarray(k_budgets, jnp.int32)
        return self._jit[key], (self.params, tokens, pos, caches,
                                block_tables,
                                k_budgets if bucket is not None else None)

    def chunk_prefill(self, tokens, positions, last_index, caches,
                      block_tables=None, *, plan: str = BASE_PLAN,
                      bucket: Optional[Tuple[int, ...]] = None,
                      k_budgets=None, donate: bool = False):
        """One ``[B, C]`` chunked-prefill step -> (logits [B,V], caches).
        ``donate`` as in ``decode``."""
        fn, args = self._chunk_call(tokens, positions, last_index,
                                    self._own(caches, donate), block_tables,
                                    plan=plan, bucket=bucket,
                                    k_budgets=k_budgets)
        return fn(*args)

    def _chunk_call(self, tokens, positions, last_index, caches,
                    block_tables=None, *, plan: str = BASE_PLAN, bucket=None,
                    k_budgets=None):
        head, cfg = self._resolve(plan, bucket)
        key = (head, "chunk", int(tokens.shape[1]), self.opts.expert_dtype)
        if key not in self._jit:
            def chunk_step(p, t, po, li, c, bt, kbud):
                return models.chunk_prefill_fn(
                    p, cfg, t, po, c, last_index=li, block_tables=bt,
                    mesh=self.mesh, opts=self.opts, k_budgets=kbud)
            self._jit[key] = jax.jit(chunk_step, donate_argnums=4)
        if bucket is not None:
            k_budgets = jnp.asarray(k_budgets, jnp.int32)
        return self._jit[key], (self.params, tokens, positions, last_index,
                                caches, block_tables,
                                k_budgets if bucket is not None else None)

    def compiled_text(self, kind: str, *args, **kw) -> str:
        """HLO text of the compiled graph ``decode``/``chunk_prefill``
        would run for these arguments (same key, same specialization) --
        what a caller inspects to see which kernels a step really calls
        (``tpu_custom_call`` on TPU)."""
        call = {"decode": self._decode_call,
                "chunk_prefill": self._chunk_call}[kind]
        fn, fn_args = call(*args, **kw)
        return fn.lower(*fn_args).compile().as_text()

    def whole_prefill(self, tokens, positions, caches, *,
                      plan: str = BASE_PLAN):
        """Legacy per-request ``[1, L]`` prefill -> (logits [1,V], caches).

        ``caches`` is a fresh 1-slot cache; the caller scatters it into its
        slot (mamba fallback -- see kv_cache.scatter_slot).  Single-request
        width means the plan is always homogeneous here.
        """
        cfg = self.plans[plan]
        key = (plan, "prefill", int(tokens.shape[1]),
               self.opts.expert_dtype)
        if key not in self._jit:
            def prefill_step(p, t, po, c):
                return models.prefill_fn(
                    p, cfg, {"tokens": t, "positions": po}, c,
                    mesh=self.mesh, opts=self.opts)
            self._jit[key] = jax.jit(prefill_step)
        return self._jit[key](self.params, tokens, positions, caches)
