"""Continuous-batching serving engine front-end.

Sequence state is pluggable per layer kind (models.state_providers): full
attention pages O(S) KV blocks, sliding-window layers keep a fixed ring of
``ceil(window/block_size)+1`` blocks written modulo the ring, and rwkv6 /
mamba2 layers keep O(1) per-slot state slabs — so the engine serves the
full, sliding, ssm, AND hybrid families through one scheduler and one
block-table layout. Admission charges the per-kind block cost (max over
kinds; recurrent layers are free) and prefix caching stays on exactly for
the all-full-attention configs where block aliasing is sound.

Wires the host-side scheduler + block-pool bookkeeping to two jitted device
functions over the per-kind sequence state:

  * ``paged_prefill_packed`` — up to ``prefills_per_step`` prompt chunks of
    DIFFERENT requests packed into one segment-masked call, padded to a
    declared (chunk-length x num-segments) bucket. Every bucket is compiled
    once at engine construction (``_warmup_prefill``), so steady-state
    serving never traces a new prefill variant.
  * ``paged_decode_step``  — one token for EVERY decoding slot at once; new
    requests join and finished requests leave the batch between steps without
    recompilation (shapes are fixed at max_slots).

All per-slot batch state (next token, sequence lengths, active mask, block
tables) is DEVICE-resident and greedy sampling happens inside the jitted
step, so the steady-state decode loop is a single dispatch per step with no
host round-trip — the python scheduler runs ahead of the device and steps
pipeline. Host↔device traffic happens only at request lifecycle events
(admit / prefill chunk / finish) and for requests that need host-side
decisions (temperature sampling, stop_token scanning). Generated tokens are
recorded as whole per-step vectors and materialized once at drain.

Prefix caching (``EngineConfig.prefix_caching``, on by default): fully
prefilled prompt blocks are published to the pool's prefix index under
chained token hashes; a new request's longest cached block-aligned prefix is
aliased read-only into its table at admission and only the uncached tail is
prefilled. Because a block's KV content is a deterministic function of the
token prefix it covers, aliased blocks are bitwise identical to what the
request would have recomputed — greedy outputs stay bit-identical to
``serve.generate`` with caching on or off. A fully-cached prompt triggers
one copy-on-write block duplication (``copy_block_fn``) so the final prompt
token can be re-run privately for its logits.

A ``ShardingPlan`` may be passed for multi-device serving: params are placed
by the plan's rules and all device steps run under the plan context so
activation constraints apply.

Telemetry (``EngineConfig.telemetry``, on by default; see
``serving.telemetry`` and the README's Telemetry section): every request's
lifecycle (arrive/admit/prefix_hit/prefill_chunk/first_token/decode_token/
deliver/finish) is traced with monotonic timestamps and the engine step
number, all engine and pool counters live in one metrics registry
(``Engine.stats`` remains as a back-compat read-only view), and the jitted
step fns are wrapped to count unique trace keys (distinct compiled
variants). The host loop runs under ``jax.profiler.TraceAnnotation``
spans that cover every moment of a step: ``engine/step`` (``step=n``)
holds ``engine/schedule`` (admission, slot-state updates, batch building),
one span per device dispatch (``engine/prefill``, ``engine/decode``,
``engine/verify``, ``engine/copy_block``, ``engine/reset_slot``) and
``engine/emit`` (token records, finishes, end-of-step metrics);
``engine/sync`` marks each transfer of step-vector values to the host and
``engine/add_request`` each submission. Nothing blocks for telemetry's
sake, and it never changes emitted tokens: greedy outputs are bit-identical
with it on or off.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import parallelism as par
from repro.kernels.quantize import KVQuantConfig
from repro.models import state_providers as SP
from repro.models import transformer as T
from repro.serving import telemetry as TM
from repro.serving.engine import spec as SPEC
from repro.serving.engine.oversub import OversubConfig, SLOPolicy
from repro.serving.engine.paged_cache import BlockPool
from repro.serving.engine.spec import SpecConfig
from repro.serving.engine.scheduler import (DECODING, FINISHED, PREFILLING,
                                            Request, Scheduler,
                                            chunk_buckets_for,
                                            segment_buckets_for)


@dataclass(frozen=True)
class EngineConfig:
    block_size: int = 16
    num_blocks: int = 128
    max_blocks_per_seq: int = 16        # block-table width P
    max_slots: int = 8                  # max concurrent sequences
    prefill_chunk: int = 32             # prompt tokens per prefill call
    prefills_per_step: int = 1          # chunks interleaved per engine step
    prefix_caching: bool = True         # alias cached prompt-prefix blocks
    attn_impl: Optional[str] = None     # paged attention "kernel" | "ref";
                                        #   None = the platform's choice
                                        #   (repro.kernels.platform)
    telemetry: bool = True              # lifecycle tracing + metrics registry
    prefill_buckets: tuple = ()         # chunk-length buckets; () = one
                                        #   bucket of prefill_chunk tokens
    packed_prefill: bool = True         # pack chunks into one prefill call
    oversub: Optional[OversubConfig] = None   # optimistic admission + victim
                                        #   preemption (engine.oversub);
                                        #   None = conservative reservation
    spec: Optional[SpecConfig] = None   # speculative decoding (engine.spec):
                                        #   k-token draft + multi-query verify
                                        #   replaces the one-token decode step
    kv_quant: Optional[KVQuantConfig] = None  # int8 paged KV + per-vector f32
                                        #   scales, dequantized inside the
                                        #   paged Pallas kernels

    def __post_init__(self):
        # keep the config hashable for the compiled-step cache even when a
        # caller declares the buckets as a list
        object.__setattr__(self, "prefill_buckets",
                           tuple(self.prefill_buckets))


def _build_step_fns(cfg, e: EngineConfig, plan):
    """The jitted device functions. Cached per (cfg, EngineConfig) for
    the plan-less path so repeated Engine construction re-uses the compiled
    steps (mirrors serve._cached_decode_step)."""
    skinds = SP.state_kinds(cfg)
    # speculative decoding enlarges every ring layer by the draft depth so a
    # verify step's K in-flight positions never overwrite a key still inside
    # someone's window — decode, prefill and verify must all index the ring
    # with the SAME enlarged modulus, hence the shared `draft` here.
    draft = e.spec.k - 1 if e.spec is not None else 0

    def in_plan(fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            if plan is None:
                return fn(*a, **kw)
            with par.plan_context(plan):
                return fn(*a, **kw)
        return wrapped

    @functools.partial(jax.jit, donate_argnums=(1,))
    @in_plan
    def decode_fn(params, pool, tokens, tables, seq_lens, active):
        positions = jnp.where(active, seq_lens, 0)
        attn_lens = jnp.where(active, seq_lens + 1, 0)
        logits, pool = T.paged_decode_step(
            cfg, params, pool, {"token": tokens}, tables, positions,
            attn_lens, impl=e.attn_impl, draft=draft)
        with jax.named_scope("head"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return greedy, logits, seq_lens + active, pool

    @functools.partial(jax.jit, donate_argnums=(1,))
    @in_plan
    def prefill_fn(params, pool, tokens, tables, starts, valids, slots):
        # packed: tokens (G, C) — one bucket-padded chunk per segment;
        # starts/valids/slots (G,). Padded segments carry valid == 0 and
        # slot == max_slots (OOB sentinel), so their writes all drop.
        logits, pool = T.paged_prefill_packed(
            cfg, params, pool, tokens, tables, starts, valids, slots,
            draft=draft)
        with jax.named_scope("head"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return greedy, logits, pool

    verify_fn = None
    if e.spec is not None:
        @functools.partial(jax.jit, donate_argnums=(1,))
        @in_plan
        def verify_fn(params, pool, tokens, tables, seq_lens, active, qlims):
            # one dispatch verifies K tokens per slot and computes the
            # greedy acceptance run in-jit (spec.verify_step)
            return SPEC.verify_step(
                cfg, params, pool, tokens, tables, seq_lens, active, qlims,
                impl=e.attn_impl)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def copy_block_fn(pool, src, dst):
        # copy-on-write: duplicate one KV block (all layers) so a request
        # whose prompt is fully cached can re-run its last token privately.
        # Only reached with prefix caching on, i.e. every leaf is a paged
        # pool indexed (n_sb, block, ...).
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pool)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def reset_slot_fn(pool, slot):
        # zero one slot's recurrent slab rows across all layers: a new
        # occupant must not see the previous request's final state
        out = {}
        for i, sk in enumerate(skinds):
            st = pool[f"l{i}"]
            if sk in ("rwkv", "mamba"):
                st = jax.tree.map(lambda a: a.at[:, slot].set(0), st)
            out[f"l{i}"] = st
        return out

    return decode_fn, prefill_fn, copy_block_fn, reset_slot_fn, verify_fn


def _step_fn_key(e: EngineConfig) -> EngineConfig:
    """Host-only fields (scheduler policy, prefix caching, telemetry, bucket
    declarations) are never read by the traced functions — the traced shapes
    come from the call-time arrays — so normalize them out of the
    compile-cache key and toggling them reuses the compiled steps. Of the
    spec config only k matters (it sets the ring modulus and the verify
    tokens width); the drafter is pure host state. ``kv_quant`` stays in the
    key: it changes the pool pytree structure the steps are traced with."""
    spec = SpecConfig(k=e.spec.k) if e.spec is not None else None
    return dataclasses.replace(e, prefix_caching=True, prefills_per_step=1,
                               telemetry=True, prefill_buckets=(),
                               packed_prefill=True, oversub=None, spec=spec)


@functools.lru_cache(maxsize=None)
def _cached_step_fns(cfg, e: EngineConfig):
    return _build_step_fns(cfg, e, None)


class Engine:
    def __init__(self, cfg, params, engine_cfg: EngineConfig = None, plan=None):
        self.cfg = cfg
        self.ecfg = engine_cfg or EngineConfig()
        self.plan = plan
        if plan is not None:
            params = jax.device_put(params, plan.param_shardings(params))
        self.params = params
        e = self.ecfg

        # speculative decoding: the drafter is host-only per-engine state;
        # the device sees only k (verify tokens width + ring slack)
        self.spec = e.spec
        self.drafter = e.spec.build_drafter() if e.spec is not None else None

        # one state provider per superblock layer (models.state_providers):
        # paged full-attention KV, ring-paged sliding-window KV, or per-slot
        # recurrent slabs. The providers drive device-state init, per-kind
        # block costs for admission, and defrag remapping.
        self.providers = SP.providers_for(
            cfg, num_blocks=e.num_blocks, block_size=e.block_size,
            max_slots=e.max_slots, max_blocks_per_seq=e.max_blocks_per_seq,
            draft=e.spec.k - 1 if e.spec is not None else 0,
            kv_quant=e.kv_quant)
        self.state_kinds = [p.kind for p in self.providers]
        self._has_recurrent = any(k in ("rwkv", "mamba")
                                  for k in self.state_kinds)
        for p in self.providers:
            if p.kind == "ring" and p.ring_pages > e.max_blocks_per_seq:
                raise ValueError(
                    f"ring needs {p.ring_pages} blocks (window "
                    f"{p.window} @ block_size {e.block_size}) > "
                    f"max_blocks_per_seq {e.max_blocks_per_seq}")
        # block aliasing is only sound when every layer's state is a pure
        # function of the token prefix — i.e. all-full-attention configs
        self.prefix_caching = (e.prefix_caching and all(
            p.supports_prefix_caching for p in self.providers))

        # telemetry: one registry + tracer + recompile tracker per engine.
        # The pool shares the registry so `pool_*` metrics export alongside
        # `engine_*`; everything is host-side and disabled-path cheap.
        self.telemetry = TM.Telemetry(enabled=e.telemetry)
        reg = self.telemetry.registry
        self._m_decode_steps = reg.counter(
            "engine_decode_steps_total", "batched decode steps dispatched")
        self._m_prefill_chunks = reg.counter(
            "engine_prefill_chunks_total", "prompt prefill chunks dispatched")
        self._m_emitted = reg.counter(
            "engine_tokens_emitted_total", "tokens emitted across requests")
        self._m_occupancy = reg.counter(
            "engine_occupancy_sum",
            "sum over decode steps of decode_batch/max_slots")
        self._m_prefix_hits = reg.counter(
            "engine_prefix_hit_tokens_total",
            "prompt tokens served from the prefix cache")
        self._m_cow = reg.counter(
            "engine_cow_copies_total", "copy-on-write block duplications")
        self._m_defrags = reg.counter(
            "engine_defrags_total", "pool defragmentation passes")
        self._m_step_syncs = reg.counter(
            "engine_step_vector_syncs_total",
            "step vectors materialized on host for stop_token scanning")
        self._m_preempts = reg.counter(
            "engine_preemptions_total", "victims evicted and rolled back")
        self._m_resumes = reg.counter(
            "engine_resumes_total", "preempted requests re-admitted")
        self._m_appends = reg.counter(
            "engine_block_appends_total",
            "blocks appended on demand to decoding sequences")
        self._m_prefill_deferrals = reg.counter(
            "engine_prefill_deferrals_total",
            "steps that skipped prefill under SLO/pool pressure")
        self._m_verify_steps = reg.counter(
            "engine_verify_steps_total", "speculative verify steps dispatched")
        self._m_draft = reg.counter(
            "engine_draft_tokens_total", "draft tokens proposed for verify")
        self._m_accepted = reg.counter(
            "engine_accepted_tokens_total", "draft tokens accepted by verify")
        self._h_accept = reg.histogram(
            "engine_spec_acceptance_rate",
            "per verify step: accepted drafts / proposed drafts")
        self._g_waiting = reg.gauge(
            "engine_waiting_requests", "requests queued awaiting admission")
        self._g_running = reg.gauge(
            "engine_running_requests", "requests prefilling or decoding")
        self._g_free_blocks = reg.gauge(
            "pool_free_blocks", "allocatable blocks (incl. cached-free)")
        self._h_queue_wait = reg.histogram(
            "engine_request_queue_wait_seconds", "arrive -> admit wait")
        self._h_ttft = reg.histogram(
            "engine_request_ttft_seconds",
            "arrive -> first token's value on the host (first deliver)")
        self._h_e2e = reg.histogram(
            "engine_request_e2e_seconds", "arrive -> finish")

        self.pool_state = T.init_paged_state(cfg, e.num_blocks, e.block_size,
                                             max_slots=e.max_slots,
                                             kv_quant=e.kv_quant)
        # HBM the int8 pools free up vs the fp32 layout (whole pool, all
        # layers and superblocks; 0 with quantization off)
        n_sb, _ = SP.superblock_layout(cfg)
        self._g_kv_quant_saved = reg.gauge(
            "kv_quant_bytes_saved_total",
            "pool bytes saved by KV quantization vs fp32 layout")
        self._g_kv_quant_saved.set(n_sb * sum(
            getattr(p, "pool_bytes_saved", lambda: 0)()
            for p in self.providers))
        on_evict = ((lambda b: self.telemetry.record(None, "evict", block=b))
                    if self.telemetry.enabled else None)
        self.block_pool = BlockPool(e.num_blocks, e.block_size,
                                    registry=reg, on_evict=on_evict)
        # declared AOT prefill buckets: every steady-state prefill dispatch
        # is padded to one of these (chunk length x segment count) shapes,
        # and ALL of them are compiled up front by _warmup_prefill
        self.chunk_buckets = chunk_buckets_for(e.prefill_chunk,
                                               e.prefill_buckets)
        self.segment_buckets = segment_buckets_for(e.prefills_per_step,
                                                   e.packed_prefill)
        self.prefill_grid = [(c, g) for c in self.chunk_buckets
                             for g in self.segment_buckets]
        self._m_bucket = {
            (c, g): reg.counter(
                f"engine_prefill_bucket_c{c}g{g}_dispatch_total",
                f"prefill dispatches at chunk bucket {c} x {g} segments")
            for c, g in self.prefill_grid}
        # oversubscription: the SLO policy flips the scheduler to optimistic
        # prompt-only reservation; the engine then appends decode blocks per
        # step and preempts victims when an append (or a higher-priority
        # queue head) can't be satisfied. Snapshot resume is sound only when
        # EVERY provider can restore from a snapshot (pure-recurrent
        # configs); hybrids recompute — the attention KV must be rebuilt
        # anyway and the slab prefill scan rebuilds recurrent state exactly.
        self._policy = SLOPolicy(e.oversub) if e.oversub is not None else None
        self._snapshot_resume = (
            e.oversub is not None and e.oversub.snapshot_resume
            and self._has_recurrent
            and all(getattr(p, "supports_snapshot_resume", False)
                    for p in self.providers))
        self.scheduler = Scheduler(
            self.block_pool, max_slots=e.max_slots,
            max_blocks_per_seq=e.max_blocks_per_seq,
            prefill_chunk=e.prefill_chunk,
            prefills_per_step=e.prefills_per_step,
            prefix_caching=self.prefix_caching,
            block_cost=self.blocks_needed,
            chunk_buckets=self.chunk_buckets,
            segment_buckets=self.segment_buckets,
            packed_prefill=e.packed_prefill,
            policy=self._policy)

        # device-resident slot state (touched from the host only at request
        # lifecycle events; the decode loop never reads it back)
        self.tables = jnp.zeros((e.max_slots, e.max_blocks_per_seq), jnp.int32)
        self.seq_lens = jnp.zeros((e.max_slots,), jnp.int32)
        self.active = jnp.zeros((e.max_slots,), bool)
        self.next_tok = jnp.zeros((e.max_slots,), jnp.int32)

        self._next_rid = 0
        self.requests: dict = {}        # rid -> Request (all ever submitted)
        self.step_count = 0             # engine steps begun

        if plan is None:
            (self._decode, self._prefill, self._copy_block, self._reset_slot,
             self._verify) = _cached_step_fns(cfg, _step_fn_key(self.ecfg))
        else:
            (self._decode, self._prefill, self._copy_block, self._reset_slot,
             self._verify) = _build_step_fns(cfg, self.ecfg, plan)
        if self.telemetry.enabled:
            # count unique trace keys per jitted step fn (the compiled-variant
            # metric the AOT warmup must hold at "declared set, counted up
            # front, zero new at serving time"); compile caching keeps
            # working — the wrapper only hashes arg shapes/dtypes
            wrap = self.telemetry.recompiles.wrap
            self._decode = wrap("decode", self._decode)
            self._prefill = wrap("prefill", self._prefill)
            self._copy_block = wrap("copy_block", self._copy_block)
            self._reset_slot = wrap("reset_slot", self._reset_slot)
            if self._verify is not None:
                self._verify = wrap("verify", self._verify)
        self._warmup_prefill()
        self._warmup_verify()

    def _warmup_prefill(self) -> None:
        """Drive every declared (chunk x segments) prefill bucket through the
        wrapped prefill fn once at construction, so steady-state serving
        never traces a new prefill variant. All-padding arguments (valids ==
        0, slot == max_slots sentinel) make every pool write a no-op — the
        donated pool round-trips bit-identical, only the executables and the
        recompile-tracker keys are created."""
        e = self.ecfg
        for c, g in self.prefill_grid:
            _, _, self.pool_state = self._device_call(
                "engine/warmup_prefill", self._prefill,
                self.params, self.pool_state, jnp.zeros((g, c), jnp.int32),
                self.tables, jnp.zeros((g,), jnp.int32),
                jnp.zeros((g,), jnp.int32),
                jnp.full((g,), e.max_slots, jnp.int32))

    def _warmup_verify(self) -> None:
        """Compile the (single) verify variant at construction, same
        all-padding trick as ``_warmup_prefill``: every slot inactive means
        qlims == 0 so every paged write drops and every recurrent slot keeps
        its old state — the donated pool round-trips bit-identical. Serving
        then never traces a new verify variant (the verify batch is always
        the full (max_slots, k) shape)."""
        if self._verify is None:
            return
        e = self.ecfg
        z = jnp.zeros((e.max_slots,), jnp.int32)
        _, _, _, _, self.pool_state = self._device_call(
            "engine/warmup_verify", self._verify,
            self.params, self.pool_state,
            jnp.zeros((e.max_slots, e.spec.k), jnp.int32), self.tables,
            z, jnp.zeros((e.max_slots,), bool), z)

    @property
    def stats(self) -> dict:
        """Back-compat snapshot of the registry-backed engine counters (the
        pre-telemetry ad-hoc dict keys). Read-only view — the full metric
        set lives in ``self.telemetry.registry``."""
        return {"decode_steps": self._m_decode_steps.value,
                "prefill_chunks": self._m_prefill_chunks.value,
                "emitted": self._m_emitted.value,
                "occupancy_sum": self._m_occupancy.value,
                "prefix_hit_tokens": self._m_prefix_hits.value,
                "cow_copies": self._m_cow.value,
                "preemptions": self._m_preempts.value,
                "resumes": self._m_resumes.value,
                "block_appends": self._m_appends.value}

    def bucket_dispatches(self) -> dict:
        """Serving-time prefill dispatch counts per declared (chunk_len,
        num_segments) bucket (warmup calls are not counted)."""
        return {k: int(m.value) for k, m in self._m_bucket.items()}

    # ----------------------------------------------------------------- API
    def blocks_needed(self, total_tokens: int) -> int:
        """Blocks one sequence of `total_tokens` reserves: the max over the
        per-kind provider costs (the block table is shared across layers)."""
        return SP.seq_blocks_needed(self.providers, total_tokens)

    def add_request(self, prompt, max_new: int, *, temperature: float = 0.0,
                    key=None, stop_token: Optional[int] = None,
                    priority: int = 0) -> int:
        """Queue a request; returns its id. `prompt`: 1-D int tokens.
        `priority` is the oversubscription class (LOWER is more important;
        ignored by the conservative scheduler).

        Validates up front that prompt + generation budget fits both the
        per-sequence block table and the whole pool, so infeasible requests
        fail here with the offending numbers instead of deep inside the
        scheduler."""
        with self.telemetry.span("engine/add_request"):
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if prompt.shape[0] < 1:
                raise ValueError("prompt must contain at least one token")
            if max_new < 1:
                raise ValueError("max_new must be >= 1")
            e = self.ecfg
            total = prompt.shape[0] + max_new
            need = self.blocks_needed(total)
            if need > e.max_blocks_per_seq:
                raise ValueError(
                    f"request infeasible: prompt_len {prompt.shape[0]} + "
                    f"max_new {max_new} = {total} tokens needs {need} blocks > "
                    f"max_blocks_per_seq {e.max_blocks_per_seq} "
                    f"(= {e.max_blocks_per_seq * e.block_size} tokens at "
                    f"block_size {e.block_size})")
            if need > e.num_blocks:
                raise ValueError(
                    f"request infeasible: prompt_len {prompt.shape[0]} + "
                    f"max_new {max_new} = {total} tokens needs {need} blocks > "
                    f"pool budget num_blocks {e.num_blocks}")
            if temperature > 0.0 and key is None:
                key = jax.random.PRNGKey(self._next_rid)
            rid = self._next_rid
            self._next_rid += 1
            req = Request(
                rid=rid, prompt=prompt, max_new=max_new,
                temperature=temperature, key=key, stop_token=stop_token,
                priority=priority, arrive_t=self.telemetry.clock())
            self.requests[rid] = req
            self.scheduler.submit(req)
            self.telemetry.record(rid, "arrive",
                                  prompt_len=int(prompt.shape[0]),
                                  max_new=int(max_new))
            return rid

    def _device_call(self, span: str, fn, *args):
        """Dispatch one jitted step under a labeled profiler span."""
        with self.telemetry.span(span):
            return fn(*args)

    def step(self) -> list:
        """One engine iteration: admit -> prefill chunk(s) -> batched decode.
        Under oversubscription the order becomes: priority preemption ->
        (policy-gated) admit + prefill -> per-sequence block growth (with
        victim preemption on append failure) -> batched decode. Returns the
        rids that emitted a token this step (token values are materialized
        lazily — read them via `drain()` / `output()`).

        The step is the profiler span ``engine/step`` (``step=n``, n the
        value of ``step_count`` before the call). Every host moment of it
        lies in exactly one child span, in order: ``engine/schedule``, the
        device dispatches, ``engine/emit``."""
        tel = self.telemetry
        n = self.step_count
        self.step_count += 1
        tel.tracer.step = n
        with tel.span("engine/step", step=n):
            return self._step()

    def _step(self) -> list:
        e = self.ecfg
        tel = self.telemetry
        pol = self._policy
        t_wall = tel.clock() if pol is not None else 0.0
        emitted = []
        sync_memo = {}                  # one host transfer per step vector

        with tel.span("engine/schedule"):
            allow_prefill = self._allow_prefill()
            admitted = self.scheduler.admit() if allow_prefill else []
        for req in admitted if self._has_recurrent else []:
            # the slot's recurrent slab rows still hold the previous
            # occupant's final state — zero them for the newcomer
            self.pool_state = self._device_call(
                "engine/reset_slot", self._reset_slot,
                self.pool_state, jnp.int32(req.slot))
        with tel.span("engine/schedule"):
            copies = [self._seat(req) for req in admitted]
            batches = self.scheduler.next_prefills() if allow_prefill else []
        for src, dst in filter(None, copies):
            # whole prefill cached: copy the last matched block into the
            # private block at its table position, then re-prefill only
            # the final token there (yields the first-token logits)
            self.pool_state = self._device_call(
                "engine/copy_block", self._copy_block,
                self.pool_state, jnp.int32(src), jnp.int32(dst))
            self._m_cow.inc()

        for batch in batches:
            # one segment-masked device call per batch: segment j carries
            # request j's chunk, padded to the (C, G) bucket; missing
            # segments get valid=0 and the out-of-range slot sentinel
            with tel.span("engine/schedule"):
                C, G = batch.chunk_len, batch.num_segments
                tokens = np.zeros((G, C), np.int32)
                starts = np.zeros((G,), np.int32)
                valids = np.zeros((G,), np.int32)
                slots = np.full((G,), e.max_slots, np.int32)
                for j, (req, start, valid) in enumerate(batch.segments):
                    tokens[j, :valid] = req.prefill_src[start:start + valid]
                    starts[j], valids[j], slots[j] = start, valid, req.slot
                args = (jnp.asarray(tokens), self.tables, jnp.asarray(starts),
                        jnp.asarray(valids), jnp.asarray(slots))
            greedy, logits, self.pool_state = self._device_call(
                "engine/prefill", self._prefill,
                self.params, self.pool_state, *args)
            with tel.span("engine/schedule"):
                self._m_bucket[(C, G)].inc()
                done = []
                for j, (req, start, valid) in enumerate(batch.segments):
                    req.prefilled += valid
                    self.scheduler.register_prefilled(req)
                    self.seq_lens = self.seq_lens.at[req.slot].set(
                        req.prefilled)
                    self._m_prefill_chunks.inc()
                    tel.record(req.rid, "prefill_chunk", start=start,
                               tokens=valid)
                    if req.prefilled == req.prefill_len:
                        done.append((j, req))
            with tel.span("engine/emit"):
                for j, req in done:
                    # prefill complete: segment j's logits yield the next
                    # token (the request's FIRST, unless this is a resumed
                    # re-prefill continuing an interrupted generation)
                    tel.record(req.rid, "decode_token" if req.got_first
                               else "first_token")
                    self._record_token(req, greedy, j, logits, j, sync_memo)
                    emitted.append(req.rid)
                    req.got_first = True
                    req.state = DECODING
                    self.active = self.active.at[req.slot].set(True)
                    if req.done:
                        self._finish(req)

        with tel.span("engine/schedule"):
            if pol is not None:
                self._grow_decode()
            batch = self.scheduler.decode_batch()
        if batch and self._verify is not None:
            emitted.extend(self._spec_decode(batch, sync_memo))
        elif batch:
            greedy, logits, self.seq_lens, self.pool_state = self._device_call(
                "engine/decode", self._decode,
                self.params, self.pool_state, self.next_tok, self.tables,
                self.seq_lens, self.active)

        with tel.span("engine/emit"):
            if batch and self._verify is None:
                self.next_tok = greedy
                self._m_decode_steps.inc()
                self._m_occupancy.inc(len(batch) / e.max_slots)
                for req in batch:
                    tel.record(req.rid, "decode_token")
                    self._record_token(req, greedy, req.slot, logits,
                                       req.slot, sync_memo)
                    emitted.append(req.rid)
                    if req.done:
                        self._finish(req)
            self._m_emitted.inc(len(emitted))
            if tel.enabled:
                self._g_waiting.set(len(self.scheduler.waiting))
                self._g_running.set(len(self.scheduler.running))
                self._g_free_blocks.set(self.block_pool.num_free)
            if pol is not None:
                pol.note_step(tel.clock() - t_wall)
        return emitted

    def _allow_prefill(self) -> bool:
        """Oversubscription's gate before admission: priority preemption,
        then whether the SLO policy lets this step admit and prefill."""
        pol = self._policy
        if pol is None:
            return True
        if pol.cfg.priority_preemption:
            self._priority_preempt()
        head_wait = None
        if self.scheduler.waiting:
            head = self.scheduler.waiting[0]
            if head.arrive_t is not None:
                head_wait = pol.clock() - head.arrive_t
        decoding = sum(1 for r in self.scheduler.running.values()
                       if r.state == DECODING)
        allow = pol.allow_prefill(
            head_wait_s=head_wait, decoding=decoding,
            pool_util=self.block_pool.utilization)
        if not allow:
            self._m_prefill_deferrals.inc()
        return allow

    def _seat(self, req: Request):
        """Host side of one admission (its slot's recurrent rows are already
        zeroed): the block-table row, lifecycle events, a snapshot resume,
        the slot's sequence length. Returns the (src, dst) blocks of the
        copy-on-write a fully cached prompt needs, else None."""
        e = self.ecfg
        tel = self.telemetry
        row = self.block_pool.table(req.rid)
        padded = np.zeros((e.max_blocks_per_seq,), np.int32)
        padded[:len(row)] = row
        self.tables = self.tables.at[req.slot].set(jnp.asarray(padded))
        self._m_prefix_hits.inc(req.prefilled)
        resumed = req.preempts > 0
        if tel.enabled:
            t_admit = tel.record(req.rid, "resume" if resumed else "admit",
                                 slot=req.slot)
            if not resumed:
                t_arrive = tel.tracer.first(req.rid, "arrive")
                if t_arrive is not None:
                    self._h_queue_wait.observe(t_admit - t_arrive)
            if req.prefilled:
                tel.record(req.rid, "prefix_hit", tokens=req.prefilled,
                           blocks=req.shared_blocks
                           + (1 if req.cow_src is not None else 0))
        if resumed:
            self._m_resumes.inc()
        if req.snapshot is not None and self._snapshot_resume:
            # pure-recurrent resume: scatter the checkpointed slab rows
            # back into the (freshly zeroed) slot and skip the re-scan —
            # prefill only covers the tokens past the snapshot
            self.pool_state = {
                f"l{i}": p.resume_restore(
                    self.pool_state[f"l{i}"], req.slot, req.snapshot[i])
                for i, p in enumerate(self.providers)}
            req.prefilled = req.snapshot_len
        req.snapshot = None
        req.snapshot_len = 0
        self.seq_lens = self.seq_lens.at[req.slot].set(req.prefilled)
        if req.cow_src is None:
            return None
        return req.cow_src, row[req.prefill_len // e.block_size - 1]

    def drain(self, max_steps: int = 100_000) -> dict:
        """Run steps until every queued request finished; returns
        {rid: np.ndarray of generated tokens} for ALL finished requests."""
        steps = 0
        while self.scheduler.has_work:
            if steps >= max_steps:      # permit exactly max_steps steps
                raise RuntimeError("drain did not converge")
            self.step()
            steps += 1
        memo = {}                       # one transfer per unique step vector
        return {rid: self._materialize(r, memo)
                for rid, r in self.requests.items() if r.state == FINISHED}

    def output(self, rid) -> np.ndarray:
        """Materialize a request's generated tokens (blocks on the device)."""
        return self._materialize(self.requests[rid], {})

    def _materialize(self, req: Request, memo: dict) -> np.ndarray:
        """The request's generated tokens on the host. Each (step vector,
        index) ref is read — one transfer per vector per ``memo``, which
        maps id(vector) to (vector, host copy) and so keeps the vector, and
        its id, alive — and replaced by its value, and the request records
        ``deliver`` for the tokens that reached the host."""
        refs = [i for i, t in enumerate(req.out_tokens)
                if isinstance(t, tuple)]
        if refs:
            with self.telemetry.span("engine/sync"):
                for i in refs:
                    vec, j = req.out_tokens[i]
                    if id(vec) not in memo:
                        memo[id(vec)] = (vec, np.asarray(vec))
                    req.out_tokens[i] = int(memo[id(vec)][1][j])
            self._delivered(req, len(refs))
        return np.asarray(req.out_tokens, np.int32)

    def _delivered(self, req: Request, n: int) -> None:
        """``n`` more of the request's token values are on the host: record
        ``deliver``; the first one closes the request's TTFT."""
        first = req.delivered == 0
        req.delivered += n
        tel = self.telemetry
        if not tel.enabled:
            return
        t = tel.record(req.rid, "deliver", tokens=n)
        if first:
            t_arrive = tel.tracer.first(req.rid, "arrive")
            if t_arrive is not None:
                self._h_ttft.observe(t - t_arrive)

    def defragment(self) -> np.ndarray:
        """Compact used KV blocks to the front of the pool and rewrite every
        live block table (host bookkeeping + one device gather per pool).
        Shared (prefix-cached) blocks move once and every owner's table
        follows; cached-free blocks keep their content. Each layer's state
        provider applies the permutation its own way (paged pools gather on
        the block axis; recurrent slabs are slot-indexed and untouched).
        Returns the applied permutation `src`
        (``new_pool[i] = old_pool[src[i]]``)."""
        src = self.block_pool.defragment()
        self._m_defrags.inc()
        self.telemetry.record(None, "defrag",
                              moved=int(np.sum(src != np.arange(len(src)))))
        src_j = jnp.asarray(src)
        self.pool_state = {
            f"l{i}": p.defrag_remap(self.pool_state[f"l{i}"], src_j)
            for i, p in enumerate(self.providers)}
        tables = np.zeros(self.tables.shape, np.int32)
        for req in self.scheduler.running.values():
            row = self.block_pool.table(req.rid)
            tables[req.slot, :len(row)] = row
        self.tables = jnp.asarray(tables)
        return src

    # -------------------------------------------------- preemption internals
    def _grow_decode(self) -> None:
        """Optimistic growth: append the block(s) each decoding sequence's
        next KV write needs, strongest request first (the policy's
        protection order). When the pool can't satisfy an append, preempt
        strictly-WEAKER victims until it can — and if none exist, the
        growing request itself is the weakest and rolls back. The maximal
        request is never victimized while anything weaker runs, so progress
        is guaranteed (its full span fits the pool, validated at submit)."""
        sched = self.scheduler
        order = sorted(sched.decode_batch(), key=SLOPolicy.protection_key)
        for req in order:
            if req.rid not in sched.running:
                continue                # became a victim earlier this pass
            need = sched.growth_need(req, extra=self._spec_horizon(req))
            if need == 0:
                continue
            while not self.block_pool.can_alloc(need):
                me = SLOPolicy.protection_key(req)
                victim = self._policy.pick_victim(
                    [r for r in sched.running.values()
                     if r is not req and SLOPolicy.protection_key(r) > me])
                self._preempt(victim if victim is not None else req)
                if victim is None:
                    break
            if req.rid in sched.running:
                fresh = sched.grow(req, extra=self._spec_horizon(req))
                old = len(self.block_pool.table(req.rid)) - len(fresh)
                self.tables = self.tables.at[
                    req.slot, old:old + len(fresh)].set(
                        jnp.asarray(fresh, jnp.int32))
                self._m_appends.inc(len(fresh))

    def _priority_preempt(self) -> None:
        """A blocked queue head may evict strictly-lower-class victims: while
        the head cannot be admitted and such a victim runs, preempt the
        weakest one. Equal-or-higher-class work is never disturbed, so this
        terminates and never inverts the class order."""
        sched = self.scheduler
        while sched.waiting and not sched.can_admit_head():
            head = sched.waiting[0]
            victim = self._policy.pick_victim(
                list(sched.running.values()), max_priority=head.priority)
            if victim is None:
                return
            self._preempt(victim)

    def _preempt(self, req: Request) -> None:
        """Evict one running request and roll it back to WAITING. Host-side
        order matters: materialize its lazy token refs (the step vectors are
        unreachable after the slot turns over), snapshot recurrent slabs if
        every provider supports restore, deactivate the slot, then let the
        scheduler register + free its blocks and requeue it."""
        self._materialize(req, {})
        if self._snapshot_resume:
            # state covers exactly the tokens processed as inputs so far:
            # seq_tokens - 1 while DECODING (the last generated token is the
            # pending input), prefilled while mid-prefill
            req.snapshot = [
                p.preempt_checkpoint(self.pool_state[f"l{i}"], req.slot)
                for i, p in enumerate(self.providers)]
            req.snapshot_len = (req.seq_tokens - 1 if req.state == DECODING
                                else req.prefilled)
        self.active = self.active.at[req.slot].set(False)
        blocks = len(self.block_pool.table(req.rid))
        if self.drafter is not None:
            self.drafter.forget(req.rid)
        self.scheduler.preempt(req)
        self._m_preempts.inc()
        self.telemetry.record(req.rid, "preempt",
                              generated=len(req.out_tokens), blocks=blocks)

    def preempt_request(self, rid: int) -> bool:
        """Force-preempt one running request (test/ops hook — the soak tests
        drive every request through at least one evict/resume cycle with
        this). Returns False if the request isn't currently running."""
        req = self.requests[rid]
        if req.state not in (PREFILLING, DECODING):
            return False
        self._preempt(req)
        return True

    # ------------------------------------------------------------- internal
    def _spec_decode(self, batch: list, sync_memo: dict) -> list:
        """One speculative decode step over the DECODING batch: host
        drafting, ONE jitted verify dispatch covering k tokens per slot,
        then a host sync of the (greedy, accepts) pair to record each
        accepted run. Spec mode inherently syncs every step — acceptance
        decides how many tokens exist, so lazy step-vector refs can't
        represent the output — which is why verify must emit > 1 token per
        step on average to win.

        Per slot the verify row is ``[pending, d1 .. d_{k-1}]``: the last
        emitted (true) token plus the drafter's guesses for the next k-1
        stream positions. ``qlims`` caps accepted tokens AND KV writes at
        what the request may still emit, so writes never pass the block
        reservation; temperature requests run with qlims == 1 (one
        guaranteed token whose value the host samples — the device only
        commits the pending token's KV, which is correct regardless of the
        sampled value)."""
        e = self.ecfg
        tel = self.telemetry
        k = e.spec.k
        emitted = []
        with tel.span("engine/schedule"):
            tokens = np.zeros((e.max_slots, k), np.int32)
            qlims = np.zeros((e.max_slots,), np.int32)
            plans = []
            for req in batch:
                # drafting needs the concrete stream: materialize any lazy
                # step-vector refs (at most this step's prefill-completion
                # token)
                self._materialize(req, sync_memo)
                q = (1 if req.temperature > 0.0
                     else min(k, req.max_new - len(req.out_tokens)))
                ctx = np.concatenate([req.prompt,
                                      np.asarray(req.out_tokens, np.int32)])
                tokens[req.slot, 0] = ctx[-1]
                if q > 1:
                    tokens[req.slot, 1:] = self.drafter.propose(
                        req.rid, ctx, k - 1)
                qlims[req.slot] = q
                plans.append((req, q))
            args = (jnp.asarray(tokens), self.tables, self.seq_lens,
                    self.active, jnp.asarray(qlims))
        greedy, accepts, logits, self.seq_lens, self.pool_state = \
            self._device_call("engine/verify", self._verify,
                              self.params, self.pool_state, *args)
        with tel.span("engine/sync"):
            g_host = np.asarray(greedy)
            a_host = np.asarray(accepts)
        with tel.span("engine/emit"):
            self._m_step_syncs.inc()
            self._m_decode_steps.inc()
            self._m_verify_steps.inc()
            self._m_occupancy.inc(len(batch) / e.max_slots)
            for req, q in plans:
                a = int(a_host[req.slot])
                toks = [int(t) for t in g_host[req.slot, :a]]
                if req.temperature > 0.0:
                    req.key, sub = jax.random.split(req.key)
                    with tel.span("engine/sync"):
                        toks = [int(jax.random.categorical(
                            sub, logits[req.slot, 0] / req.temperature))]
                if req.stop_token is not None and req.stop_token in toks:
                    # truncate at the stop token; the device advanced past
                    # it but the slot is freed below, so the overrun is
                    # unreachable
                    toks = toks[:toks.index(req.stop_token) + 1]
                req.out_tokens.extend(toks)
                emitted.append(req.rid)
                drafted, accepted = max(q - 1, 0), max(a - 1, 0)
                self._m_draft.inc(drafted)
                self._m_accepted.inc(accepted)
                if drafted:
                    self._h_accept.observe(accepted / drafted)
                tel.record(req.rid, "verify", drafted=drafted,
                           accepted=accepted)
                tel.record(req.rid, "decode_token", tokens=len(toks))
                self._delivered(req, len(toks))
                self._m_emitted.inc(len(toks) - 1)    # step() adds 1 per rid
                if req.done:
                    self._finish(req)
        return emitted

    def _spec_horizon(self, req: Request) -> int:
        """Extra block-growth horizon under speculation: the next verify
        step writes KV at positions ``seq_tokens-1 .. seq_tokens-2+qlims``,
        i.e. qlims-1 tokens past what the one-token decode step writes."""
        if self._verify is None or req.temperature > 0.0:
            return 0
        return min(self.ecfg.spec.k, req.max_new - len(req.out_tokens)) - 1

    def _record_token(self, req: Request, greedy_vec, greedy_idx,
                      logits, logits_idx, sync_memo: dict):
        """Record the request's next token. Greedy requests store a
        (step-vector, index) ref — no host sync; temperature / stop_token
        requests pay a host round-trip for the concrete value. `sync_memo`
        (one dict per engine step) caches materialized step vectors so
        stop_token scanning costs at most ONE transfer per step vector, not
        one per request."""
        tel = self.telemetry
        if req.temperature > 0.0:
            req.key, sub = jax.random.split(req.key)
            with tel.span("engine/sync"):
                tok = int(jax.random.categorical(
                    sub, logits[logits_idx] / req.temperature))
            self.next_tok = self.next_tok.at[req.slot].set(tok)
            req.out_tokens.append(tok)
            self._delivered(req, 1)
            return
        if req.stop_token is not None:
            if id(greedy_vec) not in sync_memo:
                with tel.span("engine/sync"):
                    sync_memo[id(greedy_vec)] = (greedy_vec,
                                                 np.asarray(greedy_vec))
                self._m_step_syncs.inc()
            host = sync_memo[id(greedy_vec)][1]
            req.out_tokens.append(int(host[greedy_idx]))
            self._delivered(req, 1)
        else:
            req.out_tokens.append((greedy_vec, greedy_idx))
        if req.state != DECODING:
            # token came from prefill logits: seed the device next-token
            # vector for the upcoming decode step
            self.next_tok = self.next_tok.at[req.slot].set(
                greedy_vec[greedy_idx])

    def _finish(self, req: Request) -> None:
        self.active = self.active.at[req.slot].set(False)
        if self.drafter is not None:
            self.drafter.forget(req.rid)
        self.scheduler.finish(req)
        tel = self.telemetry
        if tel.enabled:
            t_fin = tel.record(req.rid, "finish",
                               generated=len(req.out_tokens))
            t_arrive = tel.tracer.first(req.rid, "arrive")
            if t_arrive is not None:
                self._h_e2e.observe(t_fin - t_arrive)
