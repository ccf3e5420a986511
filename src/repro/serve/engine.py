"""Serving: cache sharding, jitted prefill/decode steps, batched engine.

``decode_step`` is the program the decode_* dry-run shapes lower: one new
token against a seq_len KV cache, fully sharded (batch over DP, heads/state
over TP).  The :class:`ServeEngine` implements continuous-batching-lite over
fixed slots — requests join free slots, finished slots are recycled — and can
route its launches through the HSA queue so serving shares the accelerator
with other producers (the paper's multi-tenancy story).

**Fused multi-token decode** (``decode_fusion=K``): one launch runs a jitted
``lax.scan`` of K decode steps with on-device sampling, so the per-launch
packet round trip (submit -> doorbell -> grant -> completion wait — Table
II's invocation row) is paid once per K tokens instead of per token.
Sampling is position-indexed per request (``fold_in(fold_in(seed_key, uid),
token_index)``), so token streams are bitwise-identical across fusion depths
— a finished slot is masked out mid-scan, never resampled, and host-side
splicing takes exactly each request's remaining budget.
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import ledger as ledger_mod
from repro.core.hsa.clock import WallClock
from repro.core.hsa.faults import CorruptPayload, FaultError, SilentCorruption
from repro.core.policy import (
    RESUME_REPREFILL,
    RESUME_SNAPSHOT,
    AdmissionPolicy,
    ChunkPolicy,
    FusionPolicy,
    IntegrityPolicy,
    PreemptionCandidate,
    PreemptionPolicy,
    PrefixPolicy,
    RetryPolicy,
    SpillCandidate,
    SpillPolicy,
)
from repro.core.reconfig import TransferEngine
from repro.dist import act
from repro.dist.sharding import ShardingRules
from repro.serve import paged as paged_mod
from repro.train.step import batch_shardings, moe_mesh_info


# ---------------------------------------------------------------------------
# cache sharding
# ---------------------------------------------------------------------------


def cache_pspecs(cfg: ArchConfig, rules: ShardingRules, cache_tree: Any,
                 global_batch: int) -> Any:
    """PartitionSpec per cache leaf, by name + divisibility.

    Layout [L, B, H, T, hd] (kv), [L, B, T, r] (latent), [L, B, H, P, N]
    (ssm), [L, B, K-1, C] (conv).  B shards over DP when divisible.  For the
    TP axis, the **first** non-batch dim divisible by the model-axis size is
    sharded: heads when they divide, otherwise the cache time axis
    (sequence-parallel KV — a kv=8 GQA cache at TP=16 must shard over T or a
    32k cache replicates 16× and decode stops fitting).  Softmax statistics
    over a T-sharded cache reduce with small [B, H] collectives — the standard
    trade.
    """
    import jax.tree_util as jtu

    mesh = rules.mesh
    model_size = mesh.shape.get("model", 1)
    dp_spec = rules.batch_pspec(global_batch, 0)[0]   # axis entry or None

    def tp_first_divisible(shape, start: int) -> list:
        parts: list = [None] * len(shape)
        if model_size <= 1:
            return parts
        for i in range(start, len(shape)):
            if shape[i] % model_size == 0 and shape[i] >= model_size:
                parts[i] = "model"
                break
        return parts

    def spec_for(path, leaf) -> P:
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if key == "pos":
            return P()
        shape = leaf.shape
        if key in ("k", "v", "mem_k", "mem_v", "ssm_state", "ckv", "krope",
                   "conv_tail"):
            parts = tp_first_divisible(shape, 2)
            parts[0] = None                       # layer-stack dim
            parts[1] = dp_spec                    # batch dim
            return P(*parts)
        return P(*([None] * len(shape)))

    return jtu.tree_map_with_path(spec_for, cache_tree)


def cache_shardings(cfg, rules, cache_tree, global_batch):
    pspecs = cache_pspecs(cfg, rules, cache_tree, global_batch)
    return jax.tree.map(lambda ps: NamedSharding(rules.mesh, ps), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# jitted steps
# ---------------------------------------------------------------------------


def make_prefill_step(model, rules: ShardingRules, *, global_batch: int,
                      cache_len: int | None = None):
    cfg = model.cfg
    mesh = rules.mesh
    p_shard = rules.sharding_tree(model.param_specs())
    b_shard = batch_shardings(cfg, rules, global_batch)
    minfo = moe_mesh_info(cfg, rules)

    def prefill(params, batch):
        with act.use_rules(rules):
            return model.prefill(params, batch, moe_info=minfo, cache_len=cache_len)

    logits_shard = NamedSharding(mesh, rules.batch_pspec(global_batch, 1))
    return jax.jit(
        prefill,
        in_shardings=(p_shard, b_shard),
        out_shardings=(logits_shard, None),     # cache sharding propagated
    ), p_shard, b_shard


def make_decode_step(model, rules: ShardingRules, *, global_batch: int,
                     cache_len: int, donate_cache: bool = True):
    cfg = model.cfg
    mesh = rules.mesh
    p_shard = rules.sharding_tree(model.param_specs())
    cache_tree = model.cache_specs(global_batch, cache_len)
    c_shard = cache_shardings(cfg, rules, cache_tree, global_batch)
    tok_shard = NamedSharding(mesh, rules.batch_pspec(global_batch, 1))
    logits_shard = NamedSharding(mesh, rules.batch_pspec(global_batch, 1))
    minfo = moe_mesh_info(cfg, rules, for_decode=True)

    def decode(params, tokens, cache):
        with act.use_rules(rules):
            return model.decode_step(params, tokens, cache, moe_info=minfo)

    step = jax.jit(
        decode,
        in_shardings=(p_shard, tok_shard, c_shard),
        out_shardings=(logits_shard, c_shard),
        donate_argnums=(2,) if donate_cache else (),
    )
    return step, p_shard, c_shard, cache_tree


# ---------------------------------------------------------------------------
# batched serving engine (continuous-batching-lite)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    parked: bool = False               # preempted, awaiting resume
    preemptions: int = 0               # times this request was parked
    fault_recoveries: int = 0          # fault-triggered park/requeue cycles
    # the fault that permanently killed this request (recovery budget spent)
    failed: BaseException | None = None
    # committed tokens a re-prefill resume is replaying; the engine asserts
    # regenerated tokens match this prefix bitwise, then drops it
    replay: list[int] | None = None
    # engine-clock timestamps (None until the event happens): arrival at
    # submit, first admission into a slot (queue wait ends, prefill starts),
    # first generated token, completion — the TTFT/TPOT feed
    arrival_t: float | None = None
    admit_t: float | None = None
    first_token_t: float | None = None
    finish_t: float | None = None


@dataclasses.dataclass
class _Parked:
    """A preempted request's host-side state between park and resume."""

    req: Request
    pos: int                           # cache rows at park (prompt + gen - 1)
    mode: str                          # RESUME_SNAPSHOT | RESUME_REPREFILL
    # snapshot-mode KV lives in the engine's HostArena (keyed by uid), not
    # here — the arena's budget/free-list is the single accounting point;
    # this field stays None and exists only for introspection symmetry
    snapshot: Any | None
    # engine-clock time the fault that parked this request fired (None for
    # pool-pressure parks); resume - fault_t is the request's MTTR sample
    fault_t: float | None = None
    # in-flight H2D refill handle (a reconfig.Transfer) issued by the
    # ahead-of-need pump; the resume waits on it instead of a cold DMA
    refill: Any | None = None
    # shared-prefix pages the slot held at park time: the snapshot excludes
    # them (their bytes stay resident under other readers' refcounts) and
    # the resume re-attaches them via the prefix index — or demotes to
    # replay if the prefix evaporated while parked (a CoW copy)
    shared_pages: int = 0


@dataclasses.dataclass
class _Prefilling:
    """A request mid chunked-prefill: holds a slot and a staging cache.

    ``tokens`` is the prompt padded to its bucket length — chunking runs
    over the *same* padded token array the whole-prompt path prefills, so
    every cache row (pads included) and the first-token fixup are bitwise
    identical to the unchunked engine.
    """

    req: Request
    tokens: np.ndarray                 # [b] prompt padded to bucket length
    n: int                             # real prompt length
    chunk: int                         # chunk rows per step, fixed at admit
    cache: Any                         # staging {"pos", "segments"} tree
    filled: int = 0                    # rows prefilled so far
    stalled: bool = False              # paged: last chunk unfundable


class ServeTruncated(RuntimeError):
    """``run_to_completion`` exhausted ``max_steps`` with work still pending.

    Carries the partial result so callers can't mistake truncation for
    completion — and distinguishes *why* each unfinished request is
    unfinished:

    - ``pending`` — active slots and admissible queued requests: transient,
      more steps would finish them;
    - ``parked`` — preempted mid-flight by pool pressure (generated-so-far
      tokens intact): transient, they resume when pages free up;
    - ``rejected`` — queued *or parked* requests whose worst-case page
      footprint can never fit the pool under the current admission policy:
      permanent, no number of steps completes them.  (``submit`` refuses
      these up front; they appear here only if the policy was tightened
      after submission.)
    - ``failed`` — requests killed by a hardware fault after the engine's
      recovery budget (``RetryPolicy.max_request_recoveries``) was spent:
      permanent, and raised as soon as everything else drains — the step
      loop never spins retrying them.  Each carries the fatal error on
      ``req.failed``.
    """

    def __init__(self, done: list[Request], pending: list[Request], *,
                 parked: list[Request] | tuple = (),
                 rejected: list[Request] | tuple = (),
                 failed: list[Request] | tuple = ()) -> None:
        self.done = done
        self.pending = pending
        self.parked = list(parked)
        self.rejected = list(rejected)
        self.failed = list(failed)
        super().__init__(
            f"serving truncated at max_steps: {len(done)} requests done, "
            f"{len(pending)} pending, {len(self.parked)} parked, "
            f"{len(self.rejected)} permanently rejected, "
            f"{len(self.failed)} failed to faults"
        )


class ServeEngine:
    """Fixed-slot batched decoder with slot recycling.

    Small-scale/CPU engine used by examples and tests: prompts are prefilled
    one slot at a time into the shared batch cache, all live slots decode in
    lock-step, finished slots free up for queued requests.  Sampling is greedy
    or temperature-softmax.

    **Paged KV cache** (``paged=True``): instead of a dense ``[slots,
    max_len]`` reservation per slot, KV lives in a global page pool
    (:mod:`repro.serve.paged`) addressed through per-slot block tables.
    Prefill scatters into freshly mapped pages, the fused decode scan
    carries the table and grows a sequence by one page exactly when it
    crosses a page boundary, and a finished request's pages return to the
    pool immediately.  Admission moves from "free slot?" to an
    :class:`AdmissionPolicy` over free pages and the projected growth of
    the requests already running — the concurrency ceiling becomes a
    function of *actual* sequence lengths, not the worst case.  Token
    streams are bitwise-identical to the dense engine for the same
    requests (the paged attention op gathers pages into the dense layout
    and runs the same math).
    """

    def __init__(self, model, params, *, batch_slots: int = 4,
                 max_len: int = 256, temperature: float = 0.0, seed: int = 0,
                 decode_fusion: "int | FusionPolicy" = 1,
                 hsa_queue=None, hsa_scheduler=None, producer: str = "tf-serving",
                 bucket_prompts: bool = True, min_bucket: int = 8,
                 paged: bool = False, page_size: int = 16,
                 pool_pages: int | None = None,
                 admission: AdmissionPolicy | None = None,
                 preemption: PreemptionPolicy | None = None,
                 ledger: "ledger_mod.OverheadLedger | None" = None,
                 prefill_chunk: "int | ChunkPolicy | None" = None,
                 clock=None,
                 step_time_model: "Callable[[int, int], float] | None" = None,
                 retry: "RetryPolicy | int | None" = None,
                 host_budget_bytes: int | None = None,
                 spill: "SpillPolicy | None" = None,
                 faults=None,
                 transfer_bandwidth_bytes_s: float = 8e9,
                 integrity: "IntegrityPolicy | bool | None" = None,
                 prefix: "PrefixPolicy | bool | None" = None):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.temperature = temperature
        self._queue: list[Request] = []
        self._active: dict[int, Request] = {}      # slot -> request
        self._uid = 0
        self._cache = None
        self._pos = np.zeros(batch_slots, np.int64)
        # fused multi-token decode: K tokens per launch (int) or a FusionPolicy
        # choosing K per launch from contention and remaining request length
        self.decode_fusion = decode_fusion
        self._fused_cache: dict[int, Callable[..., Any]] = {}
        # sampling is position-indexed per request: token t of request uid is
        # drawn with fold_in(fold_in(base_key, uid), t), never from a shared
        # sequential stream — so the token sequence of a request depends only
        # on (seed, uid, logits), not on admission order or fusion depth
        self._base_key = jax.random.PRNGKey(seed)
        self._slot_key = np.zeros(
            (batch_slots,) + np.shape(self._base_key), np.uint32
        )
        self._slot_tok = np.zeros(batch_slots, np.int32)
        # optional HSA routing: prefill/decode launches become queue packets so
        # serving shares the agent with other producers (paper multi-tenancy)
        if (hsa_queue is None) != (hsa_scheduler is None):
            raise ValueError("hsa_queue and hsa_scheduler must be given together")
        self._hsa_queue = hsa_queue
        self._hsa_scheduler = hsa_scheduler
        self._producer = producer
        # prompt bucketing: pad prompts to power-of-two lengths so repeated
        # serving hits the jitted prefill's trace cache instead of retracing
        # per distinct prompt length (a distinct length = a distinct role
        # signature = a re-synthesis, in paper terms).  Only safe for
        # position-indexed caches: recurrent state (SSM/conv) folds pad
        # tokens in with no pos mask to ignore them, so bucketing is forced
        # off when the model carries any.
        self.bucket_prompts = bucket_prompts and self._bucketing_safe()
        self.min_bucket = min_bucket
        self.prefill_traces = 0        # bumped at *trace* time only: the counter
        #                                the bucketing example reads before/after
        # explicit ledger for memory accounting (falls back to the queue's)
        self.ledger = ledger if ledger is not None else (
            hsa_queue.ledger if hsa_queue is not None else None
        )
        # -- paged KV cache state ------------------------------------------
        self.paged = paged
        self.page_size = page_size
        self.admission = admission if admission is not None else AdmissionPolicy()
        self.preemption = preemption if preemption is not None else PreemptionPolicy()
        # preempted requests awaiting resume, kept oldest-uid-first: parked
        # requests were admitted before anything still queued, so they also
        # resume before anything still queued (strict seniority, no starvation)
        self._parked: list[_Parked] = []
        # fault recovery: with a RetryPolicy, a launch dying to a FaultError
        # (after the scheduler's own retries) parks its requests for
        # re-prefill replay instead of raising; a request whose recovery
        # budget is spent lands in _failed and surfaces via ServeTruncated.
        # Any non-FaultError still propagates — bugs are not retried.
        self.retry = RetryPolicy.of(retry)
        self._failed: list[Request] = []
        # overcommit counters (mirrored into the ledger when one is attached)
        self.preemptions = 0
        self.resumes = 0
        self.pages_reclaimed = 0
        self.recompute_tokens = 0
        # tiered-pool counters (host arena spill/refill/demotion)
        self.spills = 0
        self.refills = 0
        self.demotions = 0
        self.replay_fallback_tokens = 0
        self.transfer_faults = 0
        if paged:
            if not self._paged_safe():
                raise ValueError(
                    "paged=True requires plain position-indexed GQA KV caches "
                    "(no MLA latent, recurrent, windowed, or cross-attn leaves)"
                )
            if page_size < 1 or max_len % page_size:
                raise ValueError(
                    f"max_len={max_len} must be a multiple of page_size={page_size}"
                )
            if pool_pages is None:
                # match the dense engine's footprint (+ the scratch page)
                pool_pages = batch_slots * (max_len // page_size) + 1
            self.allocator = paged_mod.PageAllocator(pool_pages)
            self.pool_pages = pool_pages
            self.table_pages = max_len // page_size          # table width NP
            # per-slot block tables; unmapped entries point at the scratch
            # page so masked dummy writes never touch a live page
            self._table = np.full((batch_slots, self.table_pages),
                                  paged_mod.TRASH_PAGE, np.int32)
            self._mapped = np.zeros(batch_slots, np.int64)   # pages mapped/slot
            self._projected: dict[int, int] = {}             # slot -> pages
        else:
            self.allocator = None
        # -- prefix sharing (refcounted pages + CoW block tables) ----------
        # the paper's Table II `if_not_configured` hit applied to KV state:
        # a request whose prompt prefix is already paged in attaches to the
        # resident pages at +1 refcount and prefills only its suffix
        self.prefix = PrefixPolicy.of(prefix)
        if self.prefix is not None:
            if not paged:
                raise ValueError("prefix sharing requires paged=True "
                                 "(shared pages live in the page pool)")
            if not self._chunk_safe():
                raise ValueError(
                    "prefix sharing requires chunk-exact models (plain "
                    "dense-attention GQA layers): the unshared suffix is "
                    "prefilled as one chunk over the resident prefix rows"
                )
        self._prefix_index = (
            paged_mod.PrefixIndex() if self.prefix is not None else None
        )
        self._slot_shared = np.zeros(batch_slots, np.int64)  # shared pages/slot
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_pages_saved = 0
        self.cow_copies = 0
        self._token_bytes = 0                                # set at cache build
        # concurrency trace: sustained (mean over decode steps with work
        # pending) and peak live requests — benchmarks/table7 reads these
        self._concurrency_sum = 0
        self._concurrency_n = 0
        self.peak_concurrency = 0
        # feedback staleness: producer -> (last sample count, silent rounds)
        self._wait_freshness: dict[str, tuple[int, int]] = {}
        # -- chunked prefill (continuous batching) -------------------------
        # split each prompt into prefill_chunk-row chunks that interleave
        # with fused decode in the same step(): new requests join mid-stream
        # instead of monopolizing a launch with a whole-prompt prefill
        self.chunk_policy = ChunkPolicy.of(prefill_chunk)
        if self.chunk_policy is not None and not self._chunk_safe():
            raise ValueError(
                "prefill_chunk requires plain dense-attention layers with "
                "GQA k/v caches (MoE routing and recurrent state are not "
                "row-local across chunk boundaries)"
            )
        self._prefilling: dict[int, _Prefilling] = {}
        self._staging: dict[int, Any] = {}    # slot -> reusable segments tree
        self.chunk_traces = 0                 # bumped at chunk *trace* time
        self._last_fusion_k = 1               # feeds ChunkPolicy.choose_chunk
        self._first_this_step: list[Request] = []
        # engine clock: arrival/first-token/completion timestamps ride on it;
        # a VirtualClock plus step_time_model makes latency deterministic
        # (step_time_model(prefill_tokens, decode_tokens) -> seconds, applied
        # after every step when the clock is virtual)
        self.clock = clock if clock is not None else WallClock()
        self.step_time_model = step_time_model
        # -- tiered KV pool (host arena, tier 1) ---------------------------
        # parked snapshots spill D2H into a budgeted HostArena and stream
        # back H2D ahead of need on the TransferEngine timeline; past the
        # budget, SpillPolicy demotes victims to re-prefill replay.  With
        # host_budget_bytes=None the arena is unbounded (the PR 5
        # behavior), but the accounting and refill pipeline run either way.
        self.spill = SpillPolicy.of(spill)
        self.host_budget_bytes = host_budget_bytes
        self.faults = faults
        # -- integrity layer (silent-corruption detection) ------------------
        # digests stamped at write boundaries, verified at read/transfer/
        # region boundaries, budget-scrubbed in the background.  None keeps
        # the hot path bit-for-bit free of hashing.
        self.integrity = IntegrityPolicy.of(integrity)
        if self.integrity is not None and not paged:
            raise ValueError("integrity requires paged=True "
                             "(digests are page-granular)")
        self._page_digests: dict[int, bytes] = {}   # sealed page -> digest
        # unified scrub rotation cursor: the last-scanned target, keyed as
        # (tier, id) with tier 0 = device page, tier 1 = arena uid.  Keyed
        # on *identity*, not list position: membership churn between steps
        # (pages stamped/freed, blocks parked/resumed) can delay a
        # surviving target by at most the inserted ones, never skip it.
        self._scrub_cursor: tuple[int, int] = (-1, -1)
        # injected-but-undetected corruption, the escape-accounting ground
        # truth: device pages (page -> owner uid), tainted arena entries,
        # and slots restored from tainted/corrupted payloads
        self._live_corrupt_pages: dict[int, int] = {}
        self._tainted_uids: set[int] = set()
        self._tainted_slots: set[int] = set()
        self.corruptions_injected = 0
        self.corruptions_detected = 0
        self.pages_quarantined = 0
        self.escaped_corruptions = 0
        self.scrubbed_targets = 0
        if paged:
            self.arena = paged_mod.HostArena(host_budget_bytes)
            self._xfer = TransferEngine(
                bandwidth_bytes_s=transfer_bandwidth_bytes_s,
                clock=self.clock,
                ledger=(self.ledger if self.ledger is not None
                        else ledger_mod.GLOBAL_LEDGER),
                faults=faults,
                integrity=self.integrity,
            )
            if hsa_scheduler is not None and hasattr(
                    hsa_scheduler, "register_refill_source"):
                # refills ride the scheduler's prefetch pass too: a parked
                # request nearing resume is a lookahead-window role one
                # memory tier down (non-blocking — the engine also pumps
                # itself every step, and pumping is idempotent)
                hsa_scheduler.register_refill_source(
                    self._pump_refills_external
                )
        else:
            if host_budget_bytes is not None:
                raise ValueError("host_budget_bytes requires paged=True")
            self.arena = None
            self._xfer = None
        # submit() may run on feeder threads while step() is mid-flight:
        # the queue, uid counter, and truncation classification share a lock
        self._lock = threading.RLock()

        def _traced_chunk(params, tokens, cache, start):
            self.chunk_traces += 1    # side effect runs once per new shape
            return self.model.prefill_chunk(params, tokens, cache, start=start)

        _traced_chunk.__name__ = "prefill_chunk"
        self._chunk_fn = jax.jit(_traced_chunk, static_argnames="start")
        # the bucket-pad fixup decode (one token at the true position):
        # jitted once so repeated prefills hit the trace cache instead of
        # re-lowering an eager scan per request
        self._fixup_fn = jax.jit(self.model.decode_step)
        self._fixup_fn.__name__ = "prefill_fixup"

        def _traced_prefill(params, tokens):
            self.prefill_traces += 1   # side effect runs once per new shape
            return self.model.prefill(
                params, {"tokens": tokens}, cache_len=self.max_len
            )

        _traced_prefill.__name__ = "prefill"
        self._prefill_fn = jax.jit(_traced_prefill)

    def _launch(self, fn, *args, **kwargs):
        """Run a model step directly, or as an AQL packet through the HSA queue."""
        if self._hsa_queue is None:
            return fn(*args, **kwargs)
        if kwargs:
            def call(*a):
                return fn(*a, **kwargs)
            call.__name__ = getattr(fn, "__name__", "serve_step")
        else:
            call = fn
        pkt = self._hsa_queue.call(call, *args, producer=self._producer)
        # the producer-blocked leg of the packet round trip (overlaps the
        # device execution it waits on; subtract EXEC for pure overhead)
        with ledger_mod.span(
            "hsa.wait", self._hsa_queue.ledger, ledger_mod.DISPATCH_WAIT,
            queue=self._hsa_queue.name, producer=self._producer,
            what=getattr(call, "__name__", "serve_step"),
        ):
            if getattr(self._hsa_scheduler, "running", False):
                # the scheduler's worker thread owns the consume side: never
                # run the cooperative loop concurrently, just wait for it
                pkt.completion.wait_eq(0)
            else:
                # drain only our queue: another tenant's dep-blocked packet
                # must not wedge (or deadlock) a decode step
                self._hsa_scheduler.drain(self._hsa_queue)
        if pkt.out.error is not None:
            raise pkt.out.error
        return pkt.out.value

    def submit(self, prompt: list[int], max_new_tokens: int = 32, *,
               arrival_t: float | None = None) -> int:
        """Queue a request; its uid.  ``arrival_t`` backdates the arrival
        timestamp (a trace replayer delivers arrivals at step boundaries,
        but the request arrived — and its TTFT clock started — earlier)."""
        with self._lock:
            return self._submit_locked(prompt, max_new_tokens, arrival_t)

    def _submit_locked(self, prompt: list[int], max_new_tokens: int,
                       arrival_t: float | None = None) -> int:
        self._uid += 1
        req = Request(self._uid, np.asarray(prompt, np.int32), max_new_tokens)
        req.arrival_t = arrival_t if arrival_t is not None else self.clock.now()
        if self.paged:
            if len(req.prompt) + max_new_tokens > self.max_len:
                # the block table maps exactly max_len rows: past it, decode
                # writes would clamp onto the last page and corrupt live KV
                raise ValueError(
                    f"prompt ({len(req.prompt)}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds max_len={self.max_len}"
                )
            # permanent rejection happens here, at submit: a request whose
            # *worst-case* footprint (growth_reserve-independent — under
            # overcommit it may map far more than its admission projection)
            # exceeds the pool can never complete, even with every other
            # tenant preempted.  Transient exhaustion mid-flight is handled
            # by preemption, never by an error.
            if self._never_fits(req):
                worst = self.admission.worst_case_pages(
                    len(req.prompt), max_new_tokens, self.page_size
                )
                cap = (self.allocator.total_pages
                       - self.admission.watermark_pages)
                raise ValueError(
                    f"request needs up to {worst} pages but the pool can ever "
                    f"admit at most {cap} — it would block the queue forever"
                )
        self._queue.append(req)
        return self._uid

    # -- internals ------------------------------------------------------------

    _RECURRENT_CACHE_KEYS = frozenset({"ssm_state", "conv_tail"})

    def _cache_leaf_keys(self) -> set[str] | None:
        """Leaf-key set of the model's cache tree (None if unknowable)."""
        import jax.tree_util as jtu

        try:
            specs = self.model.cache_specs(1, 8)
        except Exception:
            return None
        keys: set[str] = set()

        def visit(path, leaf):
            last = path[-1]
            keys.add(last.key if hasattr(last, "key") else str(last))

        jtu.tree_map_with_path(visit, specs["segments"])
        return keys

    def _bucketing_safe(self) -> bool:
        """True iff every cache leaf is position-indexed (decode masks by
        ``pos``, so end-padding is causally inert).  Recurrent leaves have no
        such mask, and sliding-window (ring) KV caches clip to the *last*
        window positions at prefill — which would be the pads.  Unknown cache
        layouts also decline, conservatively."""
        if getattr(self.cfg, "attn_window", None):
            return False
        keys = self._cache_leaf_keys()
        return keys is not None and not (keys & self._RECURRENT_CACHE_KEYS)

    def _bucket_len(self, n: int) -> int:
        """Next power-of-two at least ``min_bucket``, capped at ``max_len``."""
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_len)

    # -- paged KV cache internals ---------------------------------------------

    def _paged_safe(self) -> bool:
        """True iff every cache leaf is a plain GQA k/v tensor (the layouts
        :func:`repro.models.layers.attention_decode_paged` can page)."""
        if getattr(self.cfg, "attn_window", None) or self.cfg.mla is not None:
            return False
        keys = self._cache_leaf_keys()
        return keys is not None and keys <= {"k", "v"}

    def _chunk_safe(self) -> bool:
        """True iff chunked prefill is *exact* for this model: every layer a
        plain dense-attention block over GQA k/v caches.  Attention over the
        causal prefix is row-local given the cache, so chunk boundaries are
        invisible; MoE capacity routing and recurrent scans are not row-local
        and would change values across a chunk boundary."""
        if not self._paged_safe():
            return False
        return all(seg.kinds == ("dense",) for seg in self.model.segments)

    def _projected_pages(self, req: Request) -> int:
        return self.admission.projected_pages(
            len(req.prompt), req.max_new_tokens, self.page_size
        )

    def _never_fits(self, req: Request) -> bool:
        """Permanently inadmissible: the request's worst-case footprint
        exceeds what the pool can ever fund under the current admission
        policy — no amount of preemption or waiting completes it.  The one
        predicate behind submit-time rejection and truncation-time
        classification (queued and parked alike: a parked victim's restore
        floor never exceeds its worst case)."""
        worst = self.admission.worst_case_pages(
            len(req.prompt), req.max_new_tokens, self.page_size
        )
        return worst > self.allocator.total_pages - self.admission.watermark_pages

    def _projected_growth(self) -> int:
        """Pages the already-admitted requests are still projected to map.

        Chunk-prefilling slots count too: their remaining prompt rows are
        committed growth just like an active slot's remaining decode."""
        live = list(self._active) + list(self._prefilling)
        return sum(
            max(0, self._projected[slot] - int(self._mapped[slot]))
            for slot in live
        )

    def _admit_paged(self, req: Request) -> bool:
        # admission charges only the unshared pages: a resident prefix
        # costs nothing to attach (the Table II `if_not_configured` hit)
        shared = (len(self._lookup_prefix(req.prompt, req.uid))
                  if self.prefix is not None else 0)
        return self.admission.admit(
            free_pages=self.allocator.free_pages,
            projected_growth_pages=self._projected_growth(),
            request_pages=max(0, self._projected_pages(req) - shared),
        )

    def _launch_pages(self, slot: int, req: Request, k: int) -> int:
        """Mapped-page target for ``slot`` to absorb a depth-``k`` launch
        (through the last position the launch can write).  The one formula
        behind both growth *funding* (`_fund_growth`) and growth *mapping*
        (`_grow_to`) — keeping them a single computation is what makes
        mid-launch ``PagePoolExhausted`` unreachable by construction."""
        rem = req.max_new_tokens - len(req.generated)
        if rem <= 0:
            return int(self._mapped[slot])
        last_write = int(self._pos[slot]) + min(k, rem) - 1
        return min(last_write // self.page_size + 1, self.table_pages)

    def _grow_to(self, slot: int, need: int) -> None:
        """Map pages up to the ``need`` target — the on-demand growth step:
        a sequence gets its next page exactly when a launch will carry it
        across a page boundary."""
        have = int(self._mapped[slot])
        if need <= have:
            return
        pages = self.allocator.allocate(self._active[slot].uid, need - have)
        self._table[slot, have:need] = pages
        self._mapped[slot] = need

    def _release_slot(self, slot: int, req: Request) -> None:
        """Finished/cancelled request: its page *references* drop now.

        A page returns to the pool only when its last reader lets go — the
        digest stamp, the live-corruption record, and the prefix-index
        entry keyed on a physical page must all survive exactly as long as
        some block table still maps it, so they are dropped only for the
        pages the allocator actually released."""
        pages = [int(p) for p in self._table[slot, : int(self._mapped[slot])]]
        rehome = False
        if pages:
            released = self.allocator.free(req.uid, pages)
            for p in released:
                # a freed page's digest dies with its contents (the next
                # owner re-stamps); an undetected corruption on it never
                # influenced a token — latent, not escaped
                self._page_digests.pop(p, None)
                self._live_corrupt_pages.pop(p, None)
                if self._prefix_index is not None:
                    rehome = rehome or p in self._prefix_index.pages()
                    self._prefix_index.drop_page(p)
        self._tainted_slots.discard(slot)
        self._table[slot] = paged_mod.TRASH_PAGE
        self._mapped[slot] = 0
        self._projected.pop(slot, None)
        if self.prefix is not None:
            self._slot_shared[slot] = 0
            self._record_prefix_gauge()
            if rehome:
                # the released pages backed index entries, but other slots
                # may hold bitwise-identical private copies (first-wins
                # losers) — re-home the keys onto a surviving copy so a
                # prefix stays discoverable as long as *any* reader lives
                for s, r in self._active.items():
                    if s != slot:
                        self._publish_prefix(s, r)

    # -- prefix sharing: lookup / attach / publish ----------------------------

    def _lookup_prefix(self, prompt: np.ndarray, uid: int) -> list[int]:
        """Longest resident, attachable page run covering a prefix of
        ``prompt`` — the admission-time "is my prefix already configured?"
        probe.  Capped at ``(len(prompt) - 1) // page_size`` so the suffix
        prefill always computes at least the last real row (whose logits
        sample token 0); the walk stops at the first miss, ref-capped page,
        or page ``uid`` already holds."""
        if self._prefix_index is None or self._cache is None:
            return []
        cap = (len(prompt) - 1) // self.page_size
        if cap < 1:
            return []
        keys = paged_mod.prefix_page_keys(prompt, self.page_size,
                                          max_pages=cap)
        pages: list[int] = []
        for key in keys:
            p = self._prefix_index.get(key)
            if p is None:
                break
            refs = self.allocator.refcount(p)
            if refs == 0:                     # stale entry (page released)
                self._prefix_index.drop_page(p)
                break
            if refs >= self.prefix.max_refs:
                break
            if uid in self.allocator.owners_of(p):
                break
            pages.append(p)
        if len(pages) < self.prefix.min_prefix_pages:
            return []
        return pages

    def _count_prefix_lookup(self, shared: list[int]) -> None:
        self.prefix_lookups += 1
        if shared:
            self.prefix_hits += 1
            self.prefix_pages_saved += len(shared)
        if self.ledger is not None:
            self.ledger.record_prefix_lookup(
                hit=bool(shared), pages_saved=len(shared)
            )

    def _attach_prefix(self, slot: int, uid: int, pages: list[int]) -> None:
        """Map ``pages`` (a resident shared prefix) into ``slot``'s block
        table at +1 refcount each.  The caller has already reset the row."""
        for p in pages:
            self.allocator.share(p, uid)
        s = len(pages)
        self._table[slot, :s] = pages
        self._mapped[slot] = s
        self._slot_shared[slot] = s
        self._record_prefix_gauge()

    def _publish_prefix(self, slot: int, req: Request) -> None:
        """Register ``slot``'s full prompt pages in the prefix index so
        later requests with the same prefix attach instead of prefilling.
        First-wins: pages already published under a key stay published."""
        if self._prefix_index is None:
            return
        full = len(req.prompt) // self.page_size
        if full < 1:
            return
        keys = paged_mod.prefix_page_keys(req.prompt, self.page_size,
                                          max_pages=full)
        for i, key in enumerate(keys):
            self._prefix_index.publish(key, int(self._table[slot, i]))

    def _record_prefix_gauge(self) -> None:
        if self.ledger is not None and self.prefix is not None:
            self.ledger.record_prefix_sharing(
                shared_pages=self.allocator.shared_pages
            )

    # -- integrity: digests, scrubbing, corruption injection ------------------

    def _sealed_pages(self, slot: int, rows: int) -> list[int]:
        """Pages of ``slot`` whose every row is final at ``rows`` written
        rows.  The trailing partial page is still being appended to by
        decode, so it is never digested (its hash would be stale one step
        later) and never an injection target (corrupting rows that a later
        write overwrites anyway proves nothing)."""
        full = rows // self.page_size
        return [int(p) for p in self._table[slot, :full]]

    def _seal_slot_pages(self, slot: int, rows: int) -> None:
        """Stamp content digests on every sealed page of ``slot``.

        Called at each write boundary — prefill scatter, chunk scatter,
        decode commit, snapshot restore — so `_page_digests` always reflects
        the bytes a correct execution would hold."""
        if self.integrity is None:
            return
        segments = self._cache["segments"]
        for p in self._sealed_pages(slot, rows):
            # a sealed page's rows are final until the page is freed (and
            # the digest dies with it in _release_slot), so an existing
            # stamp is already correct — re-hashing would only launder an
            # injected flip into a "clean" digest
            if p not in self._page_digests:
                self._page_digests[p] = paged_mod.page_digest(segments, p)

    def _inject_corruption(self) -> None:
        """Seeded in-place bit flips on cold state, drawn once per step.

        Device-page flips target sealed pages of live slots; arena-block
        flips target parked snapshots.  Both record the page/uid so the
        engine itself can account an *escape* if undetected bytes ever
        reach a sampled token — the zero-escape assertion is honest, not
        tautological.  Injection runs regardless of ``integrity`` (that is
        how a verification-off run proves escapes actually happen)."""
        if self.faults is None or not self.paged or self._cache is None:
            return
        # device pages: sealed pages across active + fully-scattered chunk
        # rows.  Pages already corrupt are excluded — a second XOR flip would
        # restore the original bytes, silently un-corrupting the target and
        # leaving the escape accounting pointing at clean state.
        targets: list[tuple[int, int]] = []  # (slot, page)
        for slot in self._active:
            for p in self._sealed_pages(slot, int(self._pos[slot])):
                if p not in self._live_corrupt_pages:
                    targets.append((slot, p))
        for slot, entry in self._prefilling.items():
            for p in self._sealed_pages(slot, min(entry.filled, entry.n)):
                if p not in self._live_corrupt_pages:
                    targets.append((slot, p))
        if targets:
            i = self.faults.draw_corruption(
                "flip_page", [f"page[{p}]" for _, p in targets]
            )
            if i is not None:
                _, page = targets[i]
                self._cache["segments"] = paged_mod.flip_page(
                    self._cache["segments"], page
                )
                uid = self.allocator.owner_of(page)
                self._live_corrupt_pages[page] = uid if uid is not None else -1
                self.corruptions_injected += 1
                if self.ledger is not None:
                    self.ledger.record_corruption(kind="flip_page")
        # arena blocks: parked snapshots spilled to host
        uids = [u for u in self.arena.entries()
                if self.arena.load(u) is not None
                and u not in self._tainted_uids]
        if uids:
            i = self.faults.draw_corruption(
                "flip_block", [f"block[uid={u}]" for u in uids]
            )
            if i is not None:
                uid = uids[i]
                self.arena.corrupt(uid)
                self._tainted_uids.add(uid)
                self.corruptions_injected += 1
                if self.ledger is not None:
                    self.ledger.record_corruption(kind="flip_block")

    def _scrub_step(self) -> None:
        """Budgeted background audit: re-hash up to ``scrub_pages_per_step``
        cold targets against their stamped digests.  A mismatch quarantines
        the page and forces every reader through RESUME_REPREFILL — the
        same recovery lane as a PR 7 engine fault, so completed streams
        stay bitwise-identical to corruption-free runs.

        Stamped device pages and stamped arena blocks form *one* rotation,
        resumed at the first target strictly greater than the last-scanned
        (tier, id) cursor, wrapping — so under a budget smaller than the
        target count, every stamped target is audited within
        ``ceil(targets / budget)`` steps regardless of where it sits in the
        rotation, and membership churn (pages stamped/freed, blocks
        parked/resumed between steps) can never skip or double-scan a
        surviving target within a rotation.  Only *stamped* targets count:
        an unstamped arena entry (integrity off at store time) is neither
        scanned nor part of the coverage denominator."""
        if self.integrity is None or self.integrity.scrub_pages_per_step <= 0:
            return
        budget = self.integrity.scrub_pages_per_step
        t0 = self.clock.now()
        segments = self._cache["segments"] if self._cache is not None else None
        targets: list[tuple[int, int]] = []
        if segments is not None:
            targets += [(0, p) for p in sorted(self._page_digests)]
        targets += [
            (1, u) for u in sorted(self.arena.entries())
            if self.arena.digest_of(u) is not None
        ]
        scanned_pages = scanned_blocks = 0
        bad: list[int] = []
        bad_uids: list[int] = []
        if targets:
            k = min(budget, len(targets))
            idx = bisect.bisect_right(targets, self._scrub_cursor)
            scan = [targets[(idx + j) % len(targets)] for j in range(k)]
            self._scrub_cursor = scan[-1]
            for tier, tid in scan:
                if tier == 0:
                    scanned_pages += 1
                    if (paged_mod.page_digest(segments, tid)
                            != self._page_digests[tid]):
                        bad.append(tid)
                else:
                    scanned_blocks += 1
                    if not self.arena.verify(tid):
                        bad_uids.append(tid)
        self.scrubbed_targets += scanned_pages + scanned_blocks
        if self.ledger is not None:
            self.ledger.record_scrub(
                pages=scanned_pages, blocks=scanned_blocks,
                targets=len(targets),
            )
            self.ledger.record("scrub", max(0.0, self.clock.now() - t0))
        if bad:
            self._handle_corrupt_pages(bad, via="scrub")
        for uid in bad_uids:
            self.corruptions_detected += 1
            self._tainted_uids.discard(uid)
            if self.ledger is not None:
                self.ledger.record_integrity_detection(
                    via="scrub", recovered=True
                )
            entry = next(
                (e for e in self._parked if e.req.uid == uid), None
            )
            if entry is not None:
                self._demote_entry(entry)
            elif self.arena.holds(uid):
                self.arena.discard(uid)

    def _handle_corrupt_pages(self, pages: list[int], *, via: str) -> None:
        """Quarantine ``pages`` and re-prefill *every* reader from its
        prompt.

        A shared page can sit in several block tables at once, so recovery
        discovers the full reader set itself: every active reader parks
        through ``RESUME_REPREFILL`` (the PR 7 fault lane — position-
        indexed sampling replays the committed tokens bitwise-identically)
        and every mid-prefill reader aborts back to the queue.  Order
        matters: park/release first drops every reference (pages go back to
        the free list only at refcount zero), *then* quarantine pulls them
        out of circulation — the allocator only quarantines free pages,
        keeping the tiling invariant checkable.  Readers beyond the first
        of a shared page are the copy-on-write cost of sharing and are
        counted as CoW copies."""
        err = SilentCorruption(
            f"digest mismatch on page(s) {pages} (via {via})"
        )
        if self.prefix is not None:
            extra = sum(
                max(0, self.allocator.refcount(p) - 1) for p in pages
            )
            if extra:
                self.cow_copies += extra
                if self.ledger is not None:
                    self.ledger.record_prefix_cow(extra)
        for p in pages:
            self._live_corrupt_pages.pop(p, None)
            self._page_digests.pop(p, None)
        bad = set(pages)

        def reads_bad(slot: int) -> bool:
            mapped = {int(q) for q in
                      self._table[slot, : int(self._mapped[slot])]}
            return bool(bad & mapped)

        for slot in sorted(s for s in self._active if reads_bad(s)):
            req = self._active[slot]
            req.fault_recoveries += 1
            if (self.retry is not None
                    and req.fault_recoveries
                    > self.retry.max_request_recoveries):
                self._active.pop(slot)
                self._release_slot(slot, req)
                self._fail_request(req, err)
            else:
                self._park_slot(slot, mode=RESUME_REPREFILL,
                                fault_t=self.clock.now())
        for slot in sorted(s for s in self._prefilling if reads_bad(s)):
            if self.retry is not None:
                self._abort_prefill_to_queue(slot, err)
            else:
                entry = self._prefilling.pop(slot)
                self._release_slot(slot, entry.req)
                entry.req.fault_recoveries += 1
                idx = next(
                    (i for i, r in enumerate(self._queue)
                     if r.uid > entry.req.uid),
                    len(self._queue),
                )
                self._queue.insert(idx, entry.req)
        if self.prefix is not None:
            # after the parks: releasing a reader re-homes index entries
            # onto surviving copies, which may re-insert a bad page — drop
            # them last, just before they leave circulation
            for p in pages:
                self._prefix_index.drop_page(p)
        for p in pages:
            self.corruptions_detected += 1
            if self.ledger is not None:
                self.ledger.record_integrity_detection(
                    via=via, recovered=True
                )
            try:
                self.allocator.quarantine(p)
            except ValueError:
                continue  # freed page already re-allocated this step
            self.pages_quarantined += 1
            if self.ledger is not None:
                self.ledger.record_page_quarantine()

    def _record_escape(self, n: int = 1) -> None:
        self.escaped_corruptions += n
        if self.ledger is not None:
            for _ in range(n):
                self.ledger.record_escape()

    # -- preemption: park / resume lifecycle ----------------------------------

    @property
    def parked_requests(self) -> list[Request]:
        return [e.req for e in self._parked]

    def preempt(self, uid: int | None = None) -> int:
        """Park one active request, returning its pages to the pool *now*.

        With ``uid=None`` the engine's :class:`PreemptionPolicy` picks the
        victim (youngest-first by default).  This is the external-pressure
        entry point — the paper's fabric is shared "simultaneously from
        other sources", and this is how another source takes serving's
        memory back mid-flight.  The request keeps its generated-so-far
        tokens and resumes automatically once pages free up.
        """
        if not self.paged:
            raise RuntimeError("preemption requires paged=True")
        with self._lock:
            if uid is None:
                victims = self.preemption.victims(self._candidates(), 1)
                if not victims:
                    raise ValueError("no active request to preempt")
                uid = victims[0]
            slot = next(
                (s for s, r in self._active.items() if r.uid == uid), None
            )
            if slot is None:
                raise ValueError(f"request {uid} is not active")
            self._park_slot(slot)
            return uid

    def resume(self, uid: int) -> bool:
        """Force a resume attempt for a parked request.

        Returns False when the pool still cannot fund it (the request stays
        parked — re-park, never spin).  Raises ``ValueError`` if ``uid`` is
        not parked: resuming a request twice (or one that is active, done,
        or unknown) is a caller bug, not a transient condition.
        """
        with self._lock:
            entry = next(
                (e for e in self._parked if e.req.uid == uid), None
            )
            if entry is None:
                raise ValueError(
                    f"request {uid} is not parked (double resume?)"
                )
            slot = next(
                (s for s in range(self.slots)
                 if s not in self._active and s not in self._prefilling),
                None,
            )
            if slot is None:
                return False
            return self._try_resume(entry, slot)

    def _candidates(self) -> list[PreemptionCandidate]:
        return [
            PreemptionCandidate(
                uid=req.uid,
                mapped_pages=int(self._mapped[slot]),
                tokens_done=int(self._pos[slot]),
            )
            for slot, req in self._active.items()
        ]

    def _park_slot(self, slot: int, *, mode: str | None = None,
                   fault_t: float | None = None) -> None:
        """Reclaim one active request's pages; keep its progress on the host.

        ``mode`` overrides the policy's resume-mode choice (fault recovery
        forces re-prefill: device-side cache state after a failed launch is
        untrusted, so nothing is snapshotted from it); ``fault_t`` stamps the
        park as fault-triggered for MTTR accounting at resume."""
        req = self._active.pop(slot)
        t0 = time.perf_counter_ns()
        pos = int(self._pos[slot])
        if mode is None:
            mode = self.preemption.resume_mode(tokens_done=pos)
        snapshot = None
        snap_bytes = 0
        reclaimed = int(self._mapped[slot])
        shared = int(self._slot_shared[slot]) if self.prefix is not None else 0
        if mode == RESUME_SNAPSHOT:
            # only the pages holding written rows (0..pos-1) matter; pages
            # mapped ahead for a launch that never ran hold nothing.  The
            # shared-prefix pages are excluded: their bytes stay resident
            # under other readers' refcounts and the resume re-attaches
            # them through the prefix index — this is the copy-on-write
            # discipline (park copies only the private tail).
            keep = paged_mod.pages_for(pos, self.page_size)
            snapshot = paged_mod.gather_pages(
                self._cache["segments"], self._table[slot, shared:keep],
                ledger=self.ledger,
            )
            snap_bytes = paged_mod.snapshot_bytes(snapshot)
            # the snapshot spills D2H into the budgeted host arena; if the
            # store cannot be funded (budget, or a faulted transfer) the
            # park gracefully degrades to re-prefill replay — the request
            # keeps only its committed prefix and recomputes the rest
            if not self._spill_snapshot(req.uid, snapshot, snap_bytes, pos):
                mode = RESUME_REPREFILL
                snap_bytes = 0
                shared = 0
            snapshot = None                 # the arena is authoritative
        if mode == RESUME_REPREFILL:
            shared = 0                      # replay re-looks-up from scratch
        self._release_slot(slot, req)
        req.parked = True
        req.preemptions += 1
        self._parked.append(_Parked(req=req, pos=pos, mode=mode,
                                    snapshot=snapshot, fault_t=fault_t,
                                    shared_pages=shared))
        self._parked.sort(key=lambda e: e.req.uid)
        self.preemptions += 1
        self.pages_reclaimed += reclaimed
        if self.ledger is not None:
            self.ledger.record(
                ledger_mod.PREEMPT_PARK, (time.perf_counter_ns() - t0) * 1e-9,
                producer=self._producer, what=mode, uid=req.uid,
            )
            self.ledger.record_preemption(
                pages_reclaimed=reclaimed, snapshot_bytes=snap_bytes
            )

    def _try_resume(self, entry: _Parked, slot: int) -> bool:
        """Bring a parked request back into ``slot`` if the pool can fund it.

        The admission test mirrors fresh admission (projected lifetime pages
        against free minus in-flight growth), floored by what the resume
        needs *immediately* — a snapshot restore maps every written row's
        page up front, which late in a request's life can exceed the
        reserve-scaled projection.
        """
        req = entry.req
        attach: list[int] = []
        if self.prefix is not None:
            attach = self._lookup_prefix(req.prompt, req.uid)
        if entry.mode == RESUME_SNAPSHOT and entry.shared_pages > len(attach):
            # the shared prefix this snapshot leaned on evaporated (last
            # reader gone, ref-capped, or quarantined) while parked: the
            # snapshot lacks those rows, so this is the CoW moment — demote
            # to replay, which rebuilds the prefix privately (or re-shares
            # whatever the fresh lookup still finds)
            self.cow_copies += 1
            if self.ledger is not None:
                self.ledger.record_prefix_cow()
            self._demote_entry(entry)
        if entry.mode == RESUME_SNAPSHOT:
            attach = attach[: entry.shared_pages]
            need_now = (paged_mod.pages_for(entry.pos, self.page_size)
                        - len(attach))
        else:
            need_now = max(0, paged_mod.pages_for(
                len(req.prompt), self.page_size) - len(attach))
        request_pages = max(
            need_now, self._projected_pages(req) - len(attach)
        )
        if not self.admission.admit(
            free_pages=self.allocator.free_pages,
            projected_growth_pages=self._projected_growth(),
            request_pages=request_pages,
        ):
            return False                      # still full: stays parked
        t0 = time.perf_counter_ns()
        self._parked.remove(entry)
        recompute = 0
        if entry.mode == RESUME_SNAPSHOT:
            # wait on the ahead-of-need refill (only its exposed residue
            # stalls the resume); a cold resume issues the DMA on demand —
            # fully exposed, which is what the lookahead pump exists to
            # avoid.  A faulted refill retires through the transfer
            # engine's abort/backoff and demotes this entry to replay.
            x = entry.refill
            if x is None:
                x = self._issue_refill(req.uid)
            if x.error is not None:
                self.transfer_faults += 1
                self._demote_entry(entry)       # falls through to replay
            else:
                try:
                    self._xfer.wait(x)
                except CorruptPayload:
                    # the refill delivered wrong bytes (arena rot or DMA
                    # corruption caught by the payload digest): the host
                    # copy is untrusted — demote to replay, stream unharmed
                    self.transfer_faults += 1
                    self.corruptions_detected += 1
                    self._tainted_uids.discard(req.uid)
                    self._demote_entry(entry)
                else:
                    entry.refill = None
                    if x.payload is not None:
                        # the DMA's delivered bytes (corrupted or not, when
                        # verification is off) are what lands on device
                        snapshot = x.payload
                        self.arena.discard(req.uid)
                    else:
                        snapshot = self.arena.take(req.uid)
                    self.refills += 1
                    n = paged_mod.pages_for(entry.pos, self.page_size)
                    s = len(attach)
                    self._table[slot] = paged_mod.TRASH_PAGE
                    if s:
                        # the prefix rows never left the device: re-attach
                        # them at +1 refcount; only the private tail pages
                        # are allocated and DMA-restored
                        self._attach_prefix(slot, req.uid, attach)
                    pages = self.allocator.allocate(req.uid, n - s)
                    self._table[slot, s:n] = pages
                    self._mapped[slot] = n
                    self._cache["segments"] = paged_mod.restore_pages(
                        self._cache["segments"], snapshot, np.asarray(pages)
                    )
                    self._pos[slot] = entry.pos
                    self._projected[slot] = self._projected_pages(req)
                    self._slot_key[slot] = ledger_mod.host_sync(
                        self.ledger, jax.random.fold_in(self._base_key, req.uid)
                    )
                    self._seal_slot_pages(slot, entry.pos)
                    if x.corrupted or req.uid in self._tainted_uids:
                        # verification off: garbage was restored — remember
                        # it so the commit path can count the escape
                        self._tainted_uids.discard(req.uid)
                        self._tainted_slots.add(slot)
        if entry.mode == RESUME_REPREFILL:
            # re-prefill + replay: recompute the prompt cache (bitwise equal
            # to the original prefill — same fn, same inputs), rewind the
            # request, and let the normal decode path regenerate the
            # committed tokens.  Sampling is position-indexed, so the replay
            # emits the same stream bit for bit — asserted in step() against
            # ``req.replay`` as it goes.
            committed = req.replay if req.replay is not None else req.generated
            recompute = len(req.prompt) + len(committed) - 1
            req.replay = committed
            req.generated = []
            try:
                self._prefill_slot(slot, req)
            except FaultError as e:
                # the recovery prefill itself died to hardware: re-park (the
                # committed tokens live on in req.replay) or give up once the
                # recovery budget is spent — never leave it half-resumed
                if self.retry is None:
                    raise
                self._repark_faulted(entry, e)
                return False
            if req.generated[0] != committed[0]:
                raise RuntimeError(
                    f"preemption replay diverged at request {req.uid} token 0: "
                    f"re-prefill sampled {req.generated[0]}, committed "
                    f"{committed[0]}"
                )
        req.parked = False
        self._active[slot] = req
        self.resumes += 1
        self.recompute_tokens += recompute
        if self.ledger is not None:
            self.ledger.record(
                ledger_mod.PREEMPT_RESUME,
                (time.perf_counter_ns() - t0) * 1e-9,
                producer=self._producer, what=entry.mode, uid=req.uid,
            )
            self.ledger.record_resume(
                mode=entry.mode, recompute_tokens=recompute
            )
        if entry.fault_t is not None:
            # fault-triggered park now healed: park-to-resume on the engine
            # clock is this request's repair time (the MTTR feed), and the
            # replayed tokens are recovery recompute, not overcommit churn
            mttr = max(0.0, self.clock.now() - entry.fault_t)
            if self.ledger is not None:
                self.ledger.record(
                    ledger_mod.RECOVER, mttr, producer=self._producer,
                    what=entry.mode, uid=req.uid,
                )
                self.ledger.record_recovery(
                    mttr_s=mttr, recompute_tokens=recompute
                )
        return True

    # -- tiered pool: spill / refill / demotion -------------------------------

    def _spill_snapshot(self, uid: int, snapshot: Any, nbytes: int,
                        pos: int) -> bool:
        """Spill a fresh park's snapshot D2H into the host arena.

        Returns False when the park must degrade to re-prefill replay
        instead: the snapshot can never fit the budget, the D2H transfer
        faulted, or every demotable victim was already demoted and the
        store still does not fit.  With ``SpillPolicy.allow_replay=False``
        those cases raise (:class:`~repro.serve.paged.HostArenaExhausted`
        or the transfer's :class:`FaultError`) — the only configuration in
        which tiering rejects work.
        """
        arena = self.arena
        arena.configure(self.page_size * self._token_bytes)
        if not arena.can_ever_fit(nbytes):
            if not self.spill.allow_replay:
                raise paged_mod.HostArenaExhausted(
                    f"snapshot of {nbytes} B cannot ever fit host budget "
                    f"{arena.budget_bytes} B and replay is disabled"
                )
            self._count_demotion(bytes_freed=0, replay_tokens=pos)
            return False
        digest = None
        payload = None
        if self.integrity is not None:
            # stamp the content digest at the write boundary (before the
            # DMA), so corruption in the transfer *or* in the arena is
            # caught by any later check against this digest
            digest = paged_mod.tree_digest(snapshot)
            payload = snapshot
        x = self._xfer.issue("d2h", f"kv[uid={uid}]", nbytes,
                             payload=payload, digest=digest)
        if x.error is not None:
            self.transfer_faults += 1
            if isinstance(x.error, CorruptPayload):
                # the spill's payload digest failed at issue: the host copy
                # is wrong — degrading to replay keeps only trusted state
                self.corruptions_detected += 1
            if not self.spill.allow_replay:
                raise x.error
            self._count_demotion(bytes_freed=0, replay_tokens=pos)
            return False
        while not arena.fits(nbytes):
            if not self.spill.allow_replay:
                raise paged_mod.HostArenaExhausted(
                    f"store of {nbytes} B over host budget "
                    f"{arena.budget_bytes} B ({arena.used_bytes} B used) "
                    "and replay is disabled"
                )
            cands = [
                SpillCandidate(
                    uid=e.req.uid,
                    arena_bytes=arena.bytes_of(e.req.uid),
                    tokens_done=e.pos,
                )
                for e in self._parked
                if e.mode == RESUME_SNAPSHOT and arena.holds(e.req.uid)
            ]
            if not cands:
                # nothing left to demote: the incoming snapshot itself
                # degrades to replay (its d2h timeline slot is sunk cost)
                self._count_demotion(bytes_freed=0, replay_tokens=pos)
                return False
            short = arena.blocks_for(nbytes) - arena.free_blocks
            need_bytes = short * arena.block_bytes
            for v_uid in self.spill.victims(cands, need_bytes):
                self._demote_entry(
                    next(e for e in self._parked if e.req.uid == v_uid)
                )
        # store what the DMA *delivered* (a corrupt_transfer draw with
        # verification off hands back flipped bytes) under the pre-transfer
        # digest — exactly the mismatch a scrub or refill check catches
        stored = x.payload if x.payload is not None else snapshot
        arena.store(uid, stored, nbytes, digest=digest)
        if x.corrupted:
            self._tainted_uids.add(uid)
        self.spills += 1
        return True

    def _issue_refill(self, uid: int):
        """Issue the H2D refill for ``uid``'s arena entry, threading the
        stored payload + its store-time digest through the transfer so the
        DMA completion can verify what it delivered."""
        payload = digest = None
        if self.integrity is not None:
            payload = self.arena.load(uid)
            digest = self.arena.digest_of(uid)
        return self._xfer.issue(
            "h2d", f"kv[uid={uid}]", self.arena.bytes_of(uid),
            payload=payload, digest=digest,
        )

    def _demote_entry(self, entry: _Parked) -> None:
        """Demote one parked snapshot to re-prefill replay: its arena bytes
        go back to the budget, its in-flight refill (if any) is cancelled,
        and the eventual resume recomputes ``entry.pos`` rows instead of
        restoring them."""
        uid = entry.req.uid
        freed = self.arena.discard(uid) if self.arena.holds(uid) else 0
        # a tainted (corrupted-in-arena) entry demoted to replay never
        # restores its bytes: the corruption is gone with the blocks
        self._tainted_uids.discard(uid)
        if entry.refill is not None:
            self._xfer.cancel(entry.refill)
            entry.refill = None
        entry.mode = RESUME_REPREFILL
        entry.snapshot = None
        self._count_demotion(bytes_freed=freed, replay_tokens=entry.pos)

    def _count_demotion(self, *, bytes_freed: int,
                        replay_tokens: int) -> None:
        self.demotions += 1
        self.replay_fallback_tokens += replay_tokens
        if self.ledger is not None:
            self.ledger.record_demotion(
                bytes_freed=bytes_freed, replay_tokens=replay_tokens
            )

    def _pump_refills(self) -> None:
        """Issue H2D refills for the parked snapshots nearest resume.

        The ahead-of-need half of the tier: the first
        ``SpillPolicy.refill_lookahead`` parked entries (seniority order —
        exactly the order ``_step_locked`` resumes them) get their arena
        bytes queued on the transfer engine now, so by the time the resume
        runs, most of the DMA has hidden behind decode steps.  A refill
        that faults here demotes its entry to replay immediately (the
        abort/backoff already happened inside the transfer engine).
        Idempotent: entries with an in-flight refill are skipped.
        """
        if not self.paged or self._xfer is None:
            return
        for entry in list(self._parked[: self.spill.refill_lookahead]):
            if entry.mode != RESUME_SNAPSHOT or entry.refill is not None:
                continue
            uid = entry.req.uid
            if not self.arena.holds(uid):
                continue
            x = self._issue_refill(uid)
            if x.error is not None:
                self.transfer_faults += 1
                self._demote_entry(entry)
                continue
            entry.refill = x

    def _pump_refills_external(self) -> None:
        """Scheduler-driven pump (registered via
        ``Scheduler.register_refill_source``).  Never blocks: if the engine
        lock is held (a step is mid-flight on another thread), skip — the
        engine pumps itself at the end of every step anyway."""
        if not self._lock.acquire(blocking=False):
            return
        try:
            self._pump_refills()
        finally:
            self._lock.release()

    # -- fault recovery -------------------------------------------------------

    @property
    def failed_requests(self) -> list[Request]:
        """Requests permanently killed by faults (recovery budget spent)."""
        return list(self._failed)

    def _fail_request(self, req: Request, err: BaseException) -> None:
        """Recovery budget spent: the request is dead.  It moves to the
        ``failed`` bucket (surfaced by ``run_to_completion`` via
        :class:`ServeTruncated`) instead of being retried forever."""
        req.failed = err
        req.parked = False
        self._failed.append(req)
        if self.ledger is not None:
            self.ledger.record_recovery(failed=True)

    def _repark_faulted(self, entry: _Parked, err: FaultError) -> None:
        """A fault-interrupted resume goes back to the parked list (budget
        permitting) with its fault timestamp set so the eventual successful
        resume reports the full outage as MTTR."""
        req = entry.req
        req.fault_recoveries += 1
        if req.fault_recoveries > self.retry.max_request_recoveries:
            self._fail_request(req, err)
            return
        if entry.fault_t is None:
            entry.fault_t = self.clock.now()
        self._parked.append(entry)
        self._parked.sort(key=lambda e: e.req.uid)

    def _recover_decode_fault(self, err: FaultError) -> None:
        """A decode launch died to hardware after the scheduler's own
        retries: park every live slot for re-prefill replay.

        The failed launch's carry was never committed (cache, positions and
        tokens are as they were before the launch), but the device-side KV
        behind them is untrusted after a fault — so recovery forces
        ``RESUME_REPREFILL``: recompute the prompt cache from scratch and
        replay the committed tokens, which position-indexed sampling makes
        bitwise-identical to the fault-free stream.  Requests whose recovery
        budget is spent fail instead of parking.  Recovery needs the paged
        park/resume machinery and an engine RetryPolicy; otherwise the fault
        propagates unchanged (the legacy fail-loud behavior).
        """
        if self.retry is None or not self.paged:
            raise err
        now = self.clock.now()
        for slot in sorted(self._active):
            req = self._active[slot]
            req.fault_recoveries += 1
            if req.fault_recoveries > self.retry.max_request_recoveries:
                self._active.pop(slot)
                self._release_slot(slot, req)
                self._fail_request(req, err)
                continue
            self._park_slot(slot, mode=RESUME_REPREFILL, fault_t=now)

    def _abort_prefill_to_queue(self, slot: int, err: FaultError) -> None:
        """A chunked prefill's launch faulted: drop the partial staging work,
        return the request to the queue in uid order (budget permitting) —
        the re-admitted prefill recomputes every chunk from row 0, so the
        eventual stream is untouched by the fault."""
        entry = self._prefilling.pop(slot)
        req = entry.req
        if self.paged:
            self._release_slot(slot, req)
        req.fault_recoveries += 1
        if req.fault_recoveries > self.retry.max_request_recoveries:
            self._fail_request(req, err)
            return
        idx = next(
            (i for i, r in enumerate(self._queue) if r.uid > req.uid),
            len(self._queue),
        )
        self._queue.insert(idx, req)

    def _fund_growth(self, k: int) -> int:
        """Make this launch's page growth allocatable; the funded depth.

        Plans every live slot's mapping need for a depth-``k`` launch.  On a
        shortfall the cheap lever comes first: **shrink the launch** (halve
        ``k``) — a shallower scan needs fewer pages ahead and costs nothing
        but amortization, while preempting costs a victim its pages and
        possibly a full re-prefill for a launch depth that might then be
        abandoned anyway.  Only when even ``k=1`` cannot be funded does the
        engine park policy-chosen victims, one at a time with a re-plan
        between (a parked victim both frees its pages and drops its own
        need).  A lone request can always fund itself at any depth —
        ``submit`` rejected anything whose worst case exceeds the pool — so
        the loop terminates with the launch funded and
        ``PagePoolExhausted`` stays unreachable.
        """
        while True:
            needed = sum(
                max(0, self._launch_pages(slot, req, k)
                    - int(self._mapped[slot]))
                for slot, req in self._active.items()
            )
            shortfall = needed - self.allocator.free_pages
            if shortfall <= 0:
                return k
            if k > 1:
                k = (k + 1) // 2
                continue
            victims = self.preemption.victims(self._candidates(), shortfall)
            if not victims:
                return k                   # nothing to reclaim (empty batch)
            slot = next(
                s for s, r in self._active.items() if r.uid == victims[0]
            )
            self._park_slot(slot)

    def _record_memory(self) -> None:
        if self.ledger is None or self._token_bytes == 0:
            return
        used = sum(int(self._pos[s]) for s in self._active) * self._token_bytes
        if self.paged:
            reserved = (
                int(self._mapped.sum()) * self.page_size * self._token_bytes
            )
        else:
            reserved = len(self._active) * self.max_len * self._token_bytes
        self.ledger.record_memory(reserved_bytes=reserved, used_bytes=used)
        if self.arena is not None:
            self.ledger.record_host_memory(
                used_bytes=self.arena.used_bytes,
                budget_bytes=self.arena.budget_bytes,
            )

    def concurrency_stats(self) -> dict[str, float]:
        """Sustained (mean over steps with live work) and peak concurrency."""
        sustained = (
            self._concurrency_sum / self._concurrency_n
            if self._concurrency_n else 0.0
        )
        return {"sustained": sustained, "peak": float(self.peak_concurrency)}

    def _prefill_slot(self, slot: int, req: Request) -> None:
        if req.admit_t is None:
            req.admit_t = self.clock.now()
        if self.prefix is not None and self.paged:
            shared = self._lookup_prefix(req.prompt, req.uid)
            self._count_prefix_lookup(shared)
            if shared:
                self._prefill_shared(slot, req, shared)
                return
        n = len(req.prompt)
        pad = max(0, self._bucket_len(n) - n) if self.bucket_prompts else 0
        tokens = np.pad(req.prompt, (0, pad)) if pad else req.prompt
        logits, cache = self._launch(
            self._prefill_fn, self.params, jnp.asarray(tokens[None, :])
        )
        self._first_token(slot, req, logits, cache["segments"], pad=bool(pad))
        if self.paged:
            if self._cache is None:
                self._cache = {
                    "segments": paged_mod.build_pool(
                        cache["segments"], self.allocator.num_pages,
                        self.page_size,
                    )
                }
                self._token_bytes = paged_mod.pool_token_bytes(
                    self._cache["segments"]
                )
            # map pages covering the prompt and scatter the prefill KV in;
            # the page for the first decode write arrives via _grow_to
            n_store = paged_mod.pages_for(len(req.prompt), self.page_size)
            pages = self.allocator.allocate(req.uid, n_store)
            self._table[slot] = paged_mod.TRASH_PAGE
            self._table[slot, :n_store] = pages
            self._mapped[slot] = n_store
            self._projected[slot] = self._projected_pages(req)
            self._cache["segments"] = paged_mod.scatter_prefill(
                self._cache["segments"], cache["segments"],
                jnp.asarray(pages, jnp.int32), self.page_size,
            )
            self._pos[slot] = len(req.prompt)
            self._seal_slot_pages(slot, len(req.prompt))
            if self.prefix is not None:
                self._publish_prefix(slot, req)
            return
        if self._cache is None:
            # allocate the batched cache (batch axis 1 under the layer stack)
            self._cache = {
                "segments": jax.tree.map(
                    lambda x: jnp.repeat(jnp.zeros_like(x), self.slots, axis=1),
                    cache["segments"],
                )
            }
            self._token_bytes = paged_mod.pool_token_bytes(
                self._cache["segments"]
            )
        # splice the slot cache into the batch cache
        def splice(full, one):
            return jax.lax.dynamic_update_slice_in_dim(full, one, slot, axis=1)

        self._cache["segments"] = jax.tree.map(
            splice, self._cache["segments"], cache["segments"]
        )
        self._pos[slot] = len(req.prompt)

    def _prefill_shared(self, slot: int, req: Request,
                        shared: list[int]) -> None:
        """Prefill only the unshared suffix of ``req.prompt``.

        The shared pages hold exactly the KV a private prefill would have
        computed for those rows (KV row t depends only on tokens <= t, and
        page keys chain over the full token prefix), so seeding the staging
        cache from the pool and running one chunk with ``start=srows`` is
        row-for-row bitwise-identical to prefilling the whole prompt.  The
        suffix chunk always covers row n-1 (shared pages are capped at
        ``(n-1)//page_size``), so the first token's logits — chunked or
        pad-fixed — match the private path exactly.
        """
        n = len(req.prompt)
        s = len(shared)
        srows = s * self.page_size
        b = self._bucket_len(n) if self.bucket_prompts else n
        tokens = np.pad(req.prompt, (0, b - n)) if b > n else req.prompt
        staging = self._staging.get(slot)
        if staging is None:
            specs = self.model.cache_specs(1, self.max_len)["segments"]
            staging = jax.tree.map(
                lambda sp: jnp.zeros(sp.shape, sp.dtype), specs
            )
        staging = self._copy_prefix(staging, shared, req.uid)
        cache = {"pos": jnp.asarray(srows, jnp.int32), "segments": staging}
        logits, cache = self._launch(
            self._chunk_fn, self.params,
            jnp.asarray(tokens[None, srows:b]), cache, start=srows,
        )
        self._first_token(slot, req, logits, cache["segments"], pad=b > n)
        # all launches done — now mutate allocator/table state (FaultError
        # above this line leaves the engine untouched)
        n_store = paged_mod.pages_for(n, self.page_size)
        self._table[slot] = paged_mod.TRASH_PAGE
        self._attach_prefix(slot, req.uid, shared)
        priv = self.allocator.allocate(req.uid, n_store - s)
        self._table[slot, s:n_store] = priv
        self._mapped[slot] = n_store
        self._projected[slot] = self._projected_pages(req)
        self._cache["segments"] = paged_mod.scatter_chunk(
            self._cache["segments"], cache["segments"],
            jnp.asarray(self._table[slot], jnp.int32), srows, n - srows,
            self.page_size,
        )
        self._staging[slot] = cache["segments"]
        self._pos[slot] = n
        self._seal_slot_pages(slot, n)
        self._publish_prefix(slot, req)

    # -- chunked prefill (continuous batching) --------------------------------

    def _chunk_for_new(self, req: Request) -> int:
        """Chunk size a newly admitted request will prefill at (fixed for the
        request's whole prefill, so its trace set is independent of traffic)."""
        return self.chunk_policy.choose_chunk(
            live_decode=len(self._active), fusion_k=self._last_fusion_k
        )

    def _admit_chunked(self, req: Request) -> bool:
        """Paged admission for a chunked prefill: charge the *first chunk's*
        pages, not the whole prompt — the rest of the prompt is projected
        growth, reserve-scaled like decode growth.  This is what lets a new
        request join while long prompts are still streaming in."""
        chunk = self._chunk_for_new(req)
        shared = (len(self._lookup_prefix(req.prompt, req.uid))
                  if self.prefix is not None else 0)
        first = max(0, paged_mod.pages_for(
            min(len(req.prompt), shared * self.page_size + chunk),
            self.page_size,
        ) - shared)
        return self.admission.admit(
            free_pages=self.allocator.free_pages,
            projected_growth_pages=self._projected_growth(),
            request_pages=first,
        )

    def _start_chunked(self, slot: int, req: Request) -> None:
        """Admit ``req`` into ``slot`` as a chunked prefill."""
        if req.admit_t is None:
            req.admit_t = self.clock.now()
        n = len(req.prompt)
        b = self._bucket_len(n) if self.bucket_prompts else n
        tokens = np.pad(req.prompt, (0, b - n)) if b > n else req.prompt
        staging = self._staging.get(slot)
        if staging is None:
            specs = self.model.cache_specs(1, self.max_len)["segments"]
            staging = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), specs
            )
        # the staging tree is reused across occupants without re-zeroing:
        # chunk c only attends rows [0, end) written by chunks before it,
        # and decode masks rows >= pos — stale rows are never read with
        # nonzero weight, so they cannot perturb a single bit
        if self.paged and self._cache is None:
            self._cache = {
                "segments": paged_mod.build_pool(
                    staging, self.allocator.num_pages, self.page_size
                )
            }
            self._token_bytes = paged_mod.pool_token_bytes(
                self._cache["segments"]
            )
        shared: list[int] = []
        if self.prefix is not None and self.paged:
            shared = self._lookup_prefix(req.prompt, req.uid)
            self._count_prefix_lookup(shared)
        srows = len(shared) * self.page_size
        if shared:
            # seed the staging rows the shared pages cover; chunking then
            # starts at srows and never recomputes them — same bytes, fewer
            # launches (see _prefill_shared for why this is bit-exact)
            staging = self._copy_prefix(staging, shared, req.uid)
        self._prefilling[slot] = _Prefilling(
            req=req, tokens=tokens, n=n, chunk=self._chunk_for_new(req),
            cache={"pos": jnp.asarray(srows, jnp.int32), "segments": staging},
            filled=srows,
        )
        if self.paged:
            self._table[slot] = paged_mod.TRASH_PAGE
            self._mapped[slot] = 0
            if shared:
                self._attach_prefix(slot, req.uid, shared)
            self._projected[slot] = self._projected_pages(req)

    def _chunk_step(self, slot: int, entry: _Prefilling) -> int:
        """Run one prefill chunk for ``slot``; rows processed (0 = stalled)."""
        req = entry.req
        b = len(entry.tokens)
        start = entry.filled
        size = min(entry.chunk, b - start)
        if self.paged:
            # fund this chunk's pages: only rows < n are ever scattered, so
            # the mapping target is the pages covering the new *real* rows.
            # A shortfall stalls the chunk — decode keeps running and frees
            # pages; total deadlock (nothing running at all) aborts the
            # youngest prefill back to the queue in the step loop.
            need = paged_mod.pages_for(min(start + size, entry.n),
                                       self.page_size)
            have = int(self._mapped[slot])
            if need > have:
                if self.allocator.free_pages < need - have:
                    entry.stalled = True
                    return 0
                pages = self.allocator.allocate(req.uid, need - have)
                self._table[slot, have:need] = pages
                self._mapped[slot] = need
        entry.stalled = False
        toks = jnp.asarray(entry.tokens[None, start:start + size])
        logits, entry.cache = self._launch(
            self._chunk_fn, self.params, toks, entry.cache, start=start
        )
        if self.paged and start < entry.n:
            # scatter only the chunk's real rows into their pages; pad rows
            # stay in staging (decode masks them, like the unchunked path)
            count = min(start + size, entry.n) - start
            self._cache["segments"] = paged_mod.scatter_chunk(
                self._cache["segments"], entry.cache["segments"],
                jnp.asarray(self._table[slot], jnp.int32), start, count,
                self.page_size,
            )
            self._seal_slot_pages(slot, min(start + size, entry.n))
        entry.filled += size
        if entry.filled >= b:
            self._finish_chunked(slot, entry, logits)
        return size

    def _finish_chunked(self, slot: int, entry: _Prefilling, logits) -> None:
        """Prompt fully prefilled: derive token 0 exactly as the unchunked
        path would, then move the request into the decode batch."""
        req, n = entry.req, entry.n
        self._first_token(slot, req, logits, entry.cache["segments"],
                          pad=len(entry.tokens) > n)
        if not self.paged:
            if self._cache is None:
                self._cache = {
                    "segments": jax.tree.map(
                        lambda x: jnp.repeat(
                            jnp.zeros_like(x), self.slots, axis=1
                        ),
                        entry.cache["segments"],
                    )
                }
                self._token_bytes = paged_mod.pool_token_bytes(
                    self._cache["segments"]
                )

            def splice(full, one):
                return jax.lax.dynamic_update_slice_in_dim(
                    full, one, slot, axis=1
                )

            self._cache["segments"] = jax.tree.map(
                splice, self._cache["segments"], entry.cache["segments"]
            )
        self._staging[slot] = entry.cache["segments"]
        self._pos[slot] = n
        if self.paged and self.prefix is not None:
            self._publish_prefix(slot, req)
        del self._prefilling[slot]
        self._active[slot] = req
        if req.first_token_t is None:
            self._first_this_step.append(req)

    def _copy_prefix(self, staging, shared: list[int], uid: int):
        """``staging`` with its first rows seeded from the ``shared`` pool
        pages, through host memory."""
        with ledger_mod.span("engine.prefix_copy", uid=uid):
            prefix_kv = paged_mod.gather_pages(
                self._cache["segments"], np.asarray(shared, np.int64),
                ledger=self.ledger,
            )
            return paged_mod.scatter_rows(staging, prefix_kv, 0, self.page_size)

    def _first_token(self, slot: int, req: Request, logits, segments, *,
                     pad: bool) -> None:
        """Sample token 0 of ``req`` into ``slot`` from its prefill's last
        ``logits``.  With a padded prompt those logits sit at a pad position
        (end-padding is causally inert for the cached prompt positions:
        decode masks by pos), so one decode step of the last prompt token at
        its true position re-derives them; the prefill cache ``segments`` is
        kept verbatim (the decode's KV rewrite of pos n-1 is the same value
        only up to low-precision rounding)."""
        with ledger_mod.span("engine.first_token", uid=req.uid):
            if pad:
                fix_cache = {
                    "pos": jnp.asarray([len(req.prompt) - 1], jnp.int32),
                    "segments": segments,
                }
                logits, _ = self._launch(
                    self._fixup_fn, self.params,
                    jnp.asarray(req.prompt[-1:][None, :]), fix_cache,
                )
            req_key, logits = ledger_mod.host_sync(
                self.ledger,
                (jax.random.fold_in(self._base_key, req.uid), logits),
            )
            tok = self._sample_token(np.asarray(logits, np.float32)[0],
                                     req_key, 0)
            req.generated.append(int(tok))
            self._slot_key[slot] = req_key
            self._slot_tok[slot] = tok

    def _sample_token(self, logits: np.ndarray, req_key: np.ndarray,
                      t: int) -> int:
        """Sample token ``t`` of one request from its position-indexed key.

        The same formula the fused scan applies on-device — greedy argmax, or
        ``categorical(fold_in(req_key, t), logits / T)`` — so host-sampled
        tokens (the prefill's first token) and scan-sampled tokens come from
        one deterministic stream.
        """
        if self.temperature <= 0:
            return int(np.argmax(logits))
        sub = jax.random.fold_in(jnp.asarray(req_key), t)
        return int(ledger_mod.host_sync(self.ledger, jax.random.categorical(
            sub, jnp.asarray(logits) / self.temperature
        )))

    # -- fused multi-token decode -------------------------------------------------

    def _fused_decode_fn(self, k: int):
        """Jitted ``lax.scan`` of ``k`` decode steps with on-device sampling.

        Carry is ``(segments, pos, tok, counts, active, remaining)`` —
        everything per-slot.  A slot whose budget runs out mid-scan is masked:
        its position freezes, its token holds, and the emitted-validity mask
        goes False, so the host splices exactly each request's remaining
        tokens.  (The masked slot's cache rows keep absorbing dummy writes at
        its frozen position; harmless, since a recycled slot's cache is
        replaced wholesale at the next prefill.)
        """
        fn = self._fused_cache.get(k)
        if fn is not None:
            return fn
        model, temp, paged = self.model, self.temperature, self.paged

        @jax.named_scope("sample")
        def sample(logits, keys, counts):
            if temp > 0:
                sub = jax.vmap(jax.random.fold_in)(keys, counts)
                return jax.vmap(
                    lambda row, s: jax.random.categorical(s, row / temp)
                )(logits, sub)
            return jnp.argmax(logits, axis=-1)

        def fused(params, segments, table, pos, tok, keys, counts, active,
                  remaining):
            def body(carry, _):
                segments, pos, tok, counts, active, remaining = carry
                cache = {"pos": pos, "segments": segments}
                if paged:
                    # the block table rides the whole scan unchanged: page
                    # growth happens on the host *between* launches (a
                    # launch is sized so it never outruns its mapped pages)
                    cache["block_table"] = table
                    # a slot not advancing attends to one row: a free or
                    # mid-prefill slot reads the scratch page once, not its
                    # stale extent (its token is discarded below)
                    cache["lengths"] = jnp.where(active, pos + 1, 1)
                logits, new_cache = model.decode_step(
                    params, tok[:, None], cache
                )
                nxt = jnp.where(active, sample(logits, keys, counts).astype(jnp.int32), tok)
                emitted = active
                pos = jnp.where(active, pos + 1, pos)
                counts = jnp.where(active, counts + 1, counts)
                remaining = jnp.where(active, remaining - 1, remaining)
                active = active & (remaining > 0)
                carry = (new_cache["segments"], pos, nxt, counts, active, remaining)
                return carry, (nxt, emitted)

            carry0 = (segments, pos, tok, counts, active, remaining)
            carry, (toks, valid) = jax.lax.scan(body, carry0, None, length=k)
            segments, pos, tok, counts, _, _ = carry
            return segments, pos, tok, toks, valid

        fused.__name__ = f"decode_fused_k{k}" + ("_paged" if paged else "")
        # the page pool is donated: the launch updates it in place and the
        # engine keeps only the returned pool (faults are drawn before a
        # packet runs, so a faulted launch leaves the donated pool intact)
        fn = jax.jit(fused, donate_argnums=(1,) if paged else ())
        fn.__name__ = fused.__name__
        self._fused_cache[k] = fn
        return fn

    #: launches without a new foreign sample before that producer's stale
    #: p99 stops throttling K (a tenant that left must not pin fusion low)
    FEEDBACK_STALE_LAUNCHES = 8

    def _contention_ledger(self):
        """Where foreign ``dispatch_wait`` samples actually land: the shared
        queue's ledger when routed through HSA (an explicit ``ledger=`` only
        carries this engine's memory accounting), else the explicit one."""
        if self._hsa_queue is not None and self._hsa_queue.ledger is not None:
            return self._hsa_queue.ledger
        return self.ledger

    def _observed_foreign_wait(self) -> float | None:
        """Worst recent p99 ``dispatch_wait`` among *other* producers on the
        shared ledger — the feedback FusionPolicy's contention signal.

        A producer whose sample count has not moved for
        ``FEEDBACK_STALE_LAUNCHES`` consecutive launches is ignored: the
        quantile window is count-bounded, so a tenant that burst during
        warmup and then went silent would otherwise hold K down forever.
        """
        led = self._contention_ledger()
        if led is None:
            return None
        worst = None
        for prod, cats in led.producer_breakdown().items():
            if prod == self._producer:
                continue
            stat = cats.get(ledger_mod.DISPATCH_WAIT)
            if stat is None or stat.count == 0:
                continue
            last, stale = self._wait_freshness.get(prod, (-1, 0))
            stale = stale + 1 if stat.count == last else 0
            self._wait_freshness[prod] = (stat.count, stale)
            if stale >= self.FEEDBACK_STALE_LAUNCHES:
                continue
            q = led.quantile(ledger_mod.DISPATCH_WAIT, 0.99, producer=prod)
            if q is not None and (worst is None or q > worst):
                worst = q
        return worst

    def _choose_fusion(self) -> int:
        """Fusion depth for this launch: the static knob, or the policy fed
        with live contention (foreign packets pending on the shared device —
        or, in feedback mode, the observed foreign p99 dispatch_wait) and
        the mean remaining budget of the active slots."""
        remaining = [
            r.max_new_tokens - len(r.generated) for r in self._active.values()
        ]
        if isinstance(self.decode_fusion, FusionPolicy):
            depth = 0
            if self._hsa_scheduler is not None:
                depth = sum(
                    q.pending() for q in self._hsa_scheduler.queues
                    if q is not self._hsa_queue
                )
            observed = (
                self._observed_foreign_wait()
                if self.decode_fusion.feedback else None
            )
            k = self.decode_fusion.choose_k(
                queue_depth=depth,
                mean_request_len=sum(remaining) / max(1, len(remaining)),
                observed_wait_s=observed,
            )
        else:
            k = int(self.decode_fusion)
        # never scan past every live slot's budget: those steps are all-masked
        return max(1, min(k, max(remaining, default=1)))

    def step(self) -> list[Request]:
        """Admit queued requests, run one prefill chunk per chunk-prefilling
        slot, then decode up to ``decode_fusion`` tokens for all live slots
        in one fused launch.

        Returns requests completed this step.
        """
        with ledger_mod.span("engine.step"), self._lock:
            if self.ledger is not None:
                self.ledger.record_engine_step()
            return self._step_locked()

    def _step_locked(self) -> list[Request]:
        self._first_this_step = []
        if self.faults is not None or self.integrity is not None:
            # integrity: draw this step's corruption injections on the
            # pre-step state, then spend the scrub budget — detections park
            # their owners before any launch can read the bad bytes
            with ledger_mod.span("engine.integrity"):
                self._inject_corruption()
                self._scrub_step()
        with ledger_mod.span("engine.admit"):
            prefill_tokens = self._admit_locked()

        # -- chunk phase: one prefill chunk per prefilling slot, oldest
        # first (uid order), so under page pressure the senior prefill funds
        # before junior ones and always makes progress ----------------------
        if self._prefilling:
            order = sorted(
                self._prefilling,
                key=lambda s: self._prefilling[s].req.uid,
            )
            for slot in order:
                entry = self._prefilling[slot]
                try:
                    with ledger_mod.span("engine.chunk", uid=entry.req.uid):
                        prefill_tokens += self._chunk_step(slot, entry)
                except FaultError as e:
                    if self.retry is None:
                        raise
                    self._abort_prefill_to_queue(slot, e)
            if (self.paged and self._prefilling and prefill_tokens == 0
                    and not self._active):
                # every prefill stalled and nothing is decoding: no pages
                # will free on their own.  Abort the youngest prefill back
                # into the queue (uid order preserved) — its pages fund the
                # senior ones, which then always complete (a lone admitted
                # request can fund any of its chunks by construction).
                slot = max(
                    self._prefilling,
                    key=lambda s: self._prefilling[s].req.uid,
                )
                entry = self._prefilling.pop(slot)
                self._release_slot(slot, entry.req)
                idx = next(
                    (i for i, r in enumerate(self._queue)
                     if r.uid > entry.req.uid),
                    len(self._queue),
                )
                self._queue.insert(idx, entry.req)

        finished = self._decode_locked() if self._active else []

        # -- tiered pool: issue H2D refills for parked snapshots nearing
        # resume *before* the clock advances — the step's modeled time then
        # hides the DMA, which is the whole ahead-of-need point ------------
        self._pump_refills()

        # -- engine clock: advance virtual time by the step's modeled cost,
        # then stamp this step's latency events at the new now --------------
        decode_tokens = self._decode_tokens_last
        self._decode_tokens_last = 0
        if (self.step_time_model is not None
                and getattr(self.clock, "virtual", False)):
            self.clock.advance(
                self.step_time_model(prefill_tokens, decode_tokens)
            )
        now = self.clock.now()
        for req in self._first_this_step:
            req.first_token_t = now
            if self.ledger is not None and req.arrival_t is not None:
                self.ledger.record(
                    ledger_mod.TTFT, now - req.arrival_t,
                    producer=self._producer, uid=req.uid,
                )
        for req in finished:
            req.finish_t = now
            if self.ledger is not None and req.first_token_t is not None:
                self.ledger.record(
                    ledger_mod.TPOT,
                    (req.finish_t - req.first_token_t)
                    / max(1, len(req.generated) - 1),
                    producer=self._producer, uid=req.uid,
                )
        self._record_memory()
        return finished

    def _admit_locked(self) -> int:
        """Resume parked requests and admit queued ones into free slots;
        the prompt rows the whole-prompt path prefilled."""
        chunked = self.chunk_policy is not None
        prefill_tokens = 0
        for slot in range(self.slots):
            if slot in self._active or slot in self._prefilling:
                continue
            if self.paged and self._parked:
                # parked requests were admitted before anything still queued
                # (admission is FIFO), so they also resume first — and an
                # unresumable head blocks younger work exactly like the
                # queue head does.  A failed attempt is a no-op: the entry
                # stays parked until pages free up, never spins.
                entry = self._parked[0]
                with ledger_mod.span("engine.resume", uid=entry.req.uid):
                    resumed = self._try_resume(entry, slot)
                if not resumed:
                    break
                continue
            if not self._queue:
                break
            if self.paged:
                head = self._queue[0]
                admitted = (self._admit_chunked(head) if chunked
                            else self._admit_paged(head))
                if not admitted:
                    # head-of-line blocking is deliberate: skipping ahead to
                    # smaller requests would starve large ones forever
                    break
            req = self._queue.pop(0)
            if chunked:
                self._start_chunked(slot, req)
            else:
                try:
                    with ledger_mod.span("engine.prefill", uid=req.uid):
                        self._prefill_slot(slot, req)
                except FaultError as e:
                    if self.retry is None:
                        raise
                    # the prefill launch faulted before any engine state was
                    # touched: the request simply goes back to the queue head
                    # (FIFO preserved) for the next step, or fails on budget
                    req.fault_recoveries += 1
                    if req.fault_recoveries > self.retry.max_request_recoveries:
                        self._fail_request(req, e)
                    else:
                        self._queue.insert(0, req)
                    continue
                prefill_tokens += (self._bucket_len(len(req.prompt))
                                   if self.bucket_prompts else len(req.prompt))
                self._active[slot] = req
                if req.first_token_t is None:
                    self._first_this_step.append(req)
        return prefill_tokens

    #: decode tokens of the last fused launch (k × live slots) — the decode
    #: half of the step_time_model charge, reset by the step loop
    _decode_tokens_last = 0

    def _decode_locked(self) -> list[Request]:
        with ledger_mod.span("engine.decode.prep"):
            k = self._choose_fusion()
            if self.paged:
                # fund this launch's on-demand growth first: under
                # overcommit (growth_reserve < 1) the pool can run dry
                # mid-decode, and the answer is a shallower launch, then
                # preemption — never PagePoolExhausted
                k = self._fund_growth(k)
                if not self._active:
                    return []               # every live slot became a victim
                # re-cap to the survivors: if the longest-remaining slot
                # was parked, a depth-k scan past every survivor's budget
                # would run all-masked decode steps (growth stays funded —
                # it was budgeted for the larger k)
                k = max(1, min(k, max(
                    r.max_new_tokens - len(r.generated)
                    for r in self._active.values()
                )))
            n_live = len(self._active)      # post-preemption: slots decoding
            self._last_fusion_k = k
            self._decode_tokens_last = k * n_live
            self._concurrency_sum += n_live
            self._concurrency_n += 1
            self.peak_concurrency = max(self.peak_concurrency, n_live)
            counts = np.zeros(self.slots, np.int32)
            remaining = np.zeros(self.slots, np.int32)
            active = np.zeros(self.slots, bool)
            for slot, req in self._active.items():
                self._slot_tok[slot] = req.generated[-1]
                counts[slot] = len(req.generated)
                remaining[slot] = req.max_new_tokens - len(req.generated)
                active[slot] = remaining[slot] > 0
                if self.paged and remaining[slot] > 0:
                    # on-demand growth, launch-granular: map through the
                    # last position this launch can write for the slot
                    # (funded above)
                    self._grow_to(slot, self._launch_pages(slot, req, k))
            # integrity: the sealed pages this launch will read, captured
            # at pre-launch positions — decode writes only the unsealed
            # tail, so any post-launch digest mismatch on these is silent
            # corruption
            sealed_before: dict[int, list[int]] = {}
            if self.paged:
                sealed_before = {
                    slot: self._sealed_pages(slot, int(self._pos[slot]))
                    for slot in self._active
                }
            tbl = self._table if self.paged else None
            if self.paged and self.ledger is not None:
                self.ledger.record_decode_pages(
                    live=int(self._mapped[active].sum()),
                    table=self.slots * self.table_pages)
            if self.paged and self._prefilling:
                # a mid-prefill slot already has real pages mapped, but it
                # is not in this launch's active set — its masked dummy
                # writes must land on the scratch page (as an unmapped
                # slot's would), not on the chunk rows already scattered
                # into the pool
                tbl = tbl.copy()
                for pslot in self._prefilling:
                    tbl[pslot] = paged_mod.TRASH_PAGE
            # per-slot positions: continuous batching — slots joined at
            # different times decode against their own sequence positions
            args = (
                self.params, self._cache["segments"],
                jnp.asarray(tbl) if self.paged else None,
                jnp.asarray(self._pos, jnp.int32), jnp.asarray(self._slot_tok),
                jnp.asarray(self._slot_key), jnp.asarray(counts),
                jnp.asarray(active), jnp.asarray(remaining),
            )
        try:
            with ledger_mod.span("engine.decode.launch"):
                segments, pos, tok, toks, valid = self._launch(
                    self._fused_decode_fn(k), *args)
        except FaultError as e:
            self._recover_decode_fault(e)
            return []
        # the launch consumed the donated pool: only the returned one is live
        self._cache = {"segments": segments}
        with ledger_mod.span("engine.decode.commit"):
            # -- pre-commit read verification: re-hash the sealed pages
            # this launch read against their stamped digests in the *new*
            # segments.  A mismatch parks the owner at its pre-launch state
            # before the wholesale position/token commit below — corrupt
            # bytes never influence a committed token, which is what makes
            # the zero-escape assertion structural rather than probabilistic
            if self.paged:
                verify = (self.integrity is not None
                          and self.integrity.verify_reads)
                corrupt_slots: dict[int, list[int]] = {}
                for slot in list(self._active):
                    bad: list[int] = []
                    for p in sealed_before.get(slot, ()):
                        if verify and p in self._page_digests:
                            if (paged_mod.page_digest(segments, p)
                                    != self._page_digests[p]):
                                bad.append(p)
                        elif p in self._live_corrupt_pages:
                            # verification off: this launch consumed known-bad
                            # bytes — the token about to commit is divergent
                            self._live_corrupt_pages.pop(p)
                            self._record_escape()
                    if bad:
                        corrupt_slots[slot] = bad
                if corrupt_slots:
                    all_bad = sorted(
                        {p for b in corrupt_slots.values() for p in b}
                    )
                    self._handle_corrupt_pages(all_bad, via="read")
            pos, tok, toks, valid = ledger_mod.host_sync(
                self.ledger, (pos, tok, toks, valid))  # toks, valid [k, slots]
            self._pos = pos.astype(np.int64)
            self._slot_tok = tok.astype(np.int32)

            finished = []
            for slot, req in list(self._active.items()):
                if slot in self._tainted_slots:
                    # restored from a corrupted payload with verification off:
                    # the stream is divergent from the first post-restore commit
                    self._tainted_slots.discard(slot)
                    self._record_escape()
                req.generated.extend(int(t) for t in toks[valid[:, slot], slot])
                if req.replay is not None:
                    # re-prefill resume in flight: the regenerated stream must
                    # match the committed tokens bit for bit — this is the
                    # bitwise-identity claim, checked live, every launch
                    n = min(len(req.generated), len(req.replay))
                    if req.generated[:n] != req.replay[:n]:
                        raise RuntimeError(
                            f"preemption replay diverged at request {req.uid}: "
                            f"regenerated {req.generated[:n]} != committed "
                            f"{req.replay[:n]}"
                        )
                    if len(req.generated) >= len(req.replay):
                        req.replay = None      # fully replayed: normal decode
                if len(req.generated) >= req.max_new_tokens:
                    req.done = True
                    finished.append(req)
                    if self.paged:
                        self._release_slot(slot, req)
                    del self._active[slot]
            # stamp digests on pages this launch filled (write boundary: decode
            # page-crossing commit) — survivors only; finished slots released
            if self.paged and self.integrity is not None:
                for slot in self._active:
                    self._seal_slot_pages(slot, int(self._pos[slot]))
            return finished

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        """Step until every submitted request finishes; the completed requests.

        Raises :class:`ServeTruncated` if ``max_steps`` launches were not
        enough — truncation is never silently returned as success.  The
        report splits the unfinished work by cause: ``pending`` (active +
        admissible queue — transient), ``parked`` (preempted, resumable —
        transient), ``rejected`` (worst case can never fit the pool under
        the *current* admission policy — permanent; ``submit`` refuses these
        up front, so they only appear when the policy was tightened after
        submission), ``failed`` (killed by a hardware fault after the
        recovery budget was spent — permanent, raised as soon as the live
        work drains instead of spinning out ``max_steps``).  Transient pool
        exhaustion itself never raises: the engine preempts and resumes
        through it — and with an engine :class:`RetryPolicy`, transient
        hardware faults likewise never raise.
        """
        done: list[Request] = []
        for _ in range(max_steps):
            done += self.step()
            # classification and the stop check hold the lock so a feeder
            # thread's submit() lands either fully before the check (and is
            # admitted at the next step boundary) or fully after it — a
            # half-appended queue can never be misread as empty or rejected
            with self._lock:
                if (not self._active and not self._prefilling
                        and not self._queue and not self._parked):
                    if self._failed:
                        # fault-killed requests are permanent: raise the
                        # classification as soon as the live work drains
                        # instead of burning the remaining steps on no-ops
                        break
                    return done
                if not self._active and not self._prefilling and self.paged:
                    # nothing is running, so nothing will ever free pages: if
                    # the seniority head (parked before queued) can never
                    # fit, every further step is a no-op — fail fast with the
                    # classification instead of spinning out max_steps
                    head = (self._parked[0].req if self._parked
                            else self._queue[0] if self._queue else None)
                    if head is not None and self._never_fits(head):
                        break
        with self._lock:
            if (self._active or self._prefilling or self._queue
                    or self._parked or self._failed):
                pending = list(self._active.values()) + [
                    e.req for e in self._prefilling.values()
                ]
                parked: list[Request] = []
                rejected: list[Request] = []
                for req in self._queue:
                    if self.paged and self._never_fits(req):
                        rejected.append(req)
                    else:
                        pending.append(req)
                for entry in self._parked:
                    # a parked victim the tightened policy can never
                    # re-admit is just as permanently dead as an
                    # inadmissible queued request
                    if self._never_fits(entry.req):
                        rejected.append(entry.req)
                    else:
                        parked.append(entry.req)
                raise ServeTruncated(done, pending, parked=parked,
                                     rejected=rejected,
                                     failed=list(self._failed))
        return done
