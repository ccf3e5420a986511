#!/usr/bin/env python3
"""Bring-up check: the serving main path on a TPU, end to end.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded training path, four chips

One chip: Yi-6B at its published widths (bf16, random weights drawn from
``--seed``), cut to 16 of its 32 layers so that the weights and the KV pool
fit one v5e's 16 GB.  Eight requests, prompts of 384 to 2,000 tokens, two of
them sharing a 1,024-token prefix, are served by ``ServeEngine`` on its main
path (paged KV, chunked prefill, prefix sharing, fused decode), every launch
an AQL packet on the HSA queue of the TPU agent.  Then:

* the logits of one request, from chunked prefill and paged decode steps
  through the same model functions the engine jits, are compared with the
  plain float32 reference (:mod:`repro.models.reference`);
* the Pallas paged-attention kernel, compiled, is compared with its XLA
  formulation at the same widths.

Four chips: a few training steps of Yi-6B widths (2 layers) on a 2x2
(data, model) mesh and on one chip, in one process; the losses must agree.

Lines before the last describe the run: device, the kernel source each op
resolved to, compile count and seconds, peak device memory, requests and
tokens completed.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, the script exits non-zero and prints
no such line.  Timings printed here are bring-up diagnostics, not benchmark
numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"


class SmokeFailure(RuntimeError):
    """A check of this script failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServePlan:
    """What the one-chip run serves."""

    layers: int = 16                  # of Yi-6B's 32
    slots: int = 8
    max_len: int = 4096
    page_size: int = 16
    chunk: int = 512                  # prefill chunk (pow2: few traces)
    # unshared prompt lengths; the one equal to ``check_len`` is the request
    # whose logits are checked
    lengths: tuple[int, ...] = (384, 640, 1024, 1500, 2000, 900)
    prefix_len: int = 1024            # shared by two requests ...
    tails: tuple[int, int] = (200, 476)   # ... followed by these many tokens
    # 1 token from prefill + 64 from 16 fused launches of 4: every launch
    # runs at depth 4, so the fused decode compiles once
    max_new: int = 65
    fusion: int = 4
    check_len: int = 1024
    check_steps: int = 3              # paged decode steps in the logit check
    kernel_page_sizes: tuple[int, ...] = (16, 64)


@dataclasses.dataclass(frozen=True)
class TrainPlan:
    """What the four-chip run trains."""

    layers: int = 2                   # fits one chip with AdamW state
    steps: int = 3
    global_batch: int = 8
    seq: int = 256


# Tolerances.  The served model computes in bf16 (weights, activations and
# KV; f32 accumulation, softmax statistics and logits); the reference
# upcasts the same weights and runs everything in f32 at the highest
# precision, so their gap is bf16 rounding carried through every layer.
# bf16 keeps 8 significant bits (unit roundoff 2^-9 ~ 0.2%); measured over 16
# random-weight layers the relative L2 gap stays near 1% (the CPU rehearsal
# of the same check at reduced width, and the chip run recorded in
# CHANGES.md).  The bounds leave about 3x headroom over that and still fail
# a wrong cache, mask or position, which breaks the logits outright.
LOGIT_REL_L2 = 0.05          # ||served - reference|| / ||reference||
LOGIT_MIN_COSINE = 0.998     # cos(served, reference)
# Pallas vs XLA paged attention: both round their output to bf16 and the XLA
# form also rounds the probabilities to bf16 before the PV matmul.  Each
# costs up to 2^-9 relative; 1/64 of the largest output is four bf16 steps
# at that magnitude.
KERNEL_MAX_ERR_FRAC = 1 / 64
# Losses on 1 vs 4 chips: same weights, same batch; the mesh splits the
# contractions over "model" and the batch over "data", so bf16 partial sums
# round in another order.  At reduced width on 4 virtual CPU devices the gap
# was ~1e-4 relative.  The loss moves ~1e-2 relative from one synthetic batch
# to the next, so the bound sits well below that.
LOSS_REL_TOL = 2e-3


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts XLA compilations (and persistent-cache hits) while alive."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


def init_model(cfg, seed: int):
    """The model and bf16 weights drawn from ``seed``, made on the device in
    one program (no float32 staging copy of any weight)."""
    import jax

    from repro.models import build_model
    from repro.models.params import init_params

    model = build_model(cfg)
    params = jax.jit(functools.partial(init_params, model.param_specs()))(
        jax.random.key(seed)
    )
    return model, params


def make_prompts(plan: ServePlan, vocab: int, seed: int):
    """``(unshared, sharers)``: token arrays drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    unshared = [rng.integers(1, vocab, n, dtype=np.int32) for n in plan.lengths]
    prefix = rng.integers(1, vocab, plan.prefix_len, dtype=np.int32)
    sharers = [np.concatenate([prefix, rng.integers(1, vocab, t, dtype=np.int32)])
               for t in plan.tails]
    return unshared, sharers


def bucket(n: int, chunk: int) -> int:
    """The engine's prompt bucket (next power of two), in chunks."""
    b = 1
    while b < n:
        b *= 2
    return -(-b // chunk)


def log_trace(trace, log) -> None:
    from repro.core import dispatch

    counts: dict[str, int] = {}
    for _, name in trace.events:
        counts[name] = counts.get(name, 0) + 1
    log(f"dispatch: interpret={dispatch.current().interpret}; resolved "
        + ", ".join(f"{k} x{v}" for k, v in sorted(counts.items())))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve(model, params, plan: ServePlan, seed: int, log):
    """Serve the plan's requests through ``ServeEngine`` on the HSA queue of
    the default agent.  Returns the completed requests."""
    from repro.core.hsa import hsa_init, hsa_shut_down
    from repro.serve.engine import ServeEngine

    unshared, (owner, late) = make_prompts(plan, model.cfg.vocab_size, seed)
    hsa_shut_down()
    system = hsa_init(num_regions=2)
    try:
        agent = system.default_agent
        queue, sched = system.queue_of(agent), system.scheduler_of(agent)
        log(f"hsa: agent {agent.name} ({agent.device.device_kind}), "
            f"regions {[(r.name, r.size_bytes) for r in agent.regions]}")
        engine = ServeEngine(
            model, params, batch_slots=plan.slots, max_len=plan.max_len,
            decode_fusion=plan.fusion, paged=True, page_size=plan.page_size,
            prefill_chunk=plan.chunk, prefix=True, seed=seed,
            hsa_queue=queue, hsa_scheduler=sched,
        )
        for p in unshared + [owner]:
            engine.submit(p.tolist(), max_new_tokens=plan.max_new)
        # the second sharer arrives once the first (admitted at the first
        # step, one chunk per step) has prefilled and published its prefix
        # pages, and attaches to them while the first still decodes
        done = []
        for _ in range(bucket(len(owner), plan.chunk)):
            done += engine.step()
        engine.submit(late.tolist(), max_new_tokens=plan.max_new)
        done += engine.run_to_completion()
        packets = sched.stats[queue.name].dispatched
    finally:
        hsa_shut_down()

    n_req = len(unshared) + 2
    tokens = sum(len(r.generated) for r in done)
    log(f"serve: {len(done)}/{n_req} requests, {tokens} tokens generated, "
        f"{sum(len(r.prompt) for r in done)} prompt tokens; prefix hits "
        f"{engine.prefix_hits}, pages saved {engine.prefix_pages_saved}; "
        f"{packets} HSA packets on {queue.name}; peak concurrency "
        f"{engine.peak_concurrency}")
    check(len(done) == n_req, f"{n_req - len(done)} requests did not complete")
    check(all(len(r.generated) == plan.max_new for r in done),
          "a request stopped short of max_new_tokens")
    check(engine.prefix_hits >= 1, "the shared prefix was never attached")
    check(engine.prefix_pages_saved >= plan.prefix_len // plan.page_size,
          "fewer shared pages than the prefix covers")
    check(packets > 0, "no launch went through the HSA queue")
    return done


def check_logits(model, params, plan: ServePlan, request, log) -> None:
    """Prefill ``request``'s prompt in the engine's chunks, then decode
    ``check_steps`` of its generated tokens through a paged cache, with the
    model functions the engine jits; compare every logit vector with the
    float32 reference over the same tokens."""
    import jax
    import jax.numpy as jnp

    from repro.models import reference
    from repro.serve import paged as paged_mod

    cfg = model.cfg
    prompt = np.asarray(request.prompt, np.int32)
    n, ps, k = len(prompt), plan.page_size, plan.check_steps
    check(n % plan.chunk == 0, "the checked prompt must fill whole chunks")
    fed = np.asarray(request.generated[:k], np.int32)

    chunk_fn = jax.jit(model.prefill_chunk, static_argnames="start")
    specs = model.cache_specs(1, plan.max_len)["segments"]
    staging = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
    cache = {"pos": jnp.asarray(0, jnp.int32), "segments": staging}
    for start in range(0, n, plan.chunk):
        logits, cache = chunk_fn(
            params, jnp.asarray(prompt[None, start:start + plan.chunk]),
            cache, start=start,
        )
    served = [logits[0]]

    # move the prompt's KV into a page pool: page 0 is the scratch page,
    # the sequence owns pages 1.. in order
    n_pages = plan.max_len // ps
    pool = paged_mod.build_pool(cache["segments"], n_pages + 1, ps)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    pool = paged_mod.scatter_chunk(pool, cache["segments"], table, 0, n, ps)
    decode_fn = jax.jit(model.decode_step)
    pcache = {"pos": jnp.asarray([n], jnp.int32), "segments": pool,
              "block_table": table[None]}
    for t in range(k):
        logits, out = decode_fn(params, jnp.asarray(fed[None, t:t + 1]), pcache)
        pcache = {**pcache, "pos": out["pos"], "segments": out["segments"]}
        served.append(logits[0])

    tokens = jnp.asarray(np.concatenate([prompt, fed]))
    ref = jax.jit(functools.partial(reference.logits, cfg))(params, tokens)
    ref = np.asarray(ref[n - 1:], np.float32)
    served = np.stack([np.asarray(s, np.float32) for s in served])
    err = np.linalg.norm(served - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    cos = np.sum(served * ref, axis=-1) / (
        np.linalg.norm(served, axis=-1) * np.linalg.norm(ref, axis=-1)
    )
    agree = int(np.sum(served.argmax(-1) == ref.argmax(-1)))
    log(f"logits vs float32 reference (prefill row {n - 1} + {k} paged decode "
        f"steps): rel L2 {np.array2string(err, precision=5)} (bound "
        f"{LOGIT_REL_L2}), cosine {np.array2string(cos, precision=6)} (bound "
        f"{LOGIT_MIN_COSINE}), argmax agrees at {agree}/{k + 1}; engine's "
        f"first token {request.generated[0]} vs direct "
        f"{int(served[0].argmax())}")
    check(bool(np.all(np.isfinite(served))), "non-finite served logits")
    check(bool(np.all(err <= LOGIT_REL_L2)),
          f"logits off the reference: rel L2 {err.max():.4g} > {LOGIT_REL_L2}")
    check(bool(np.all(cos >= LOGIT_MIN_COSINE)),
          f"logits off the reference: cosine {cos.min():.6f}")


def check_kernel(cfg, plan: ServePlan, seed: int, *, interpret: bool,
                 trace, log) -> None:
    """Pallas paged decode attention (through dispatch, compiled unless
    ``interpret``) against the XLA formulation at the model's widths."""
    import jax
    import jax.numpy as jnp

    from repro.core import dispatch
    from repro.kernels import ops

    B, T, hd = plan.slots, plan.max_len, cfg.head_dim
    for ps in plan.kernel_page_sizes:
        n_pages = B * (T // ps) + 1
        kq, kk, kv, kt, kl = jax.random.split(jax.random.key(seed + ps), 5)
        q = jax.random.normal(kq, (B, cfg.num_heads, hd), jnp.bfloat16)
        k_pages = jax.random.normal(kk, (n_pages, cfg.num_kv_heads, ps, hd),
                                    jnp.bfloat16)
        v_pages = jax.random.normal(kv, k_pages.shape, jnp.bfloat16)
        table = (jax.random.permutation(kt, n_pages - 1) + 1).reshape(
            B, T // ps).astype(jnp.int32)
        lengths = jax.random.randint(kl, (B,), 1, T + 1).at[0].set(T)
        args = (q, k_pages, v_pages, table, lengths.astype(jnp.int32))

        pallas_fn = jax.jit(
            lambda *a: dispatch.op("paged_decode_attention", *a)
        )
        with dispatch.use(prefer=("pallas",), interpret=interpret,
                          trace=trace):
            got = np.asarray(pallas_fn(*args), np.float32)
            kernel_in_hlo = ("tpu_custom_call"
                             in pallas_fn.lower(*args).compile().as_text())
        want = np.asarray(
            jax.jit(ops.xla_paged_decode_attention)(*args), np.float32
        )
        err = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        log(f"pallas paged_decode_attention (page {ps}, B={B}, T={T}, "
            f"interpret={interpret}, kernel in compiled HLO: {kernel_in_hlo})"
            f" vs XLA: max |diff| {err:.3g} = {err / scale:.3g} of max |out| "
            f"(bound {KERNEL_MAX_ERR_FRAC:.3g})")
        check(bool(np.all(np.isfinite(got))), "non-finite kernel output")
        check(err <= KERNEL_MAX_ERR_FRAC * scale,
              f"Pallas kernel off XLA at page size {ps}")
        check(interpret or kernel_in_hlo, "no Pallas kernel in the program")


def train_compare(cfg, plan: TrainPlan, seed: int, log) -> None:
    """The same steps on a 2x2 (data, model) mesh and on one chip."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.launch.train import train

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs four devices, found {len(devices)}")
    losses = {}
    for shape in ((2, 2), (1, 1)):
        mesh = make_mesh(shape, ("data", "model"))
        t0 = time.perf_counter()
        params, opt_state, report = train(
            cfg, mesh, steps=plan.steps, global_batch=plan.global_batch,
            seq=plan.seq, seed=seed,
        )
        losses[shape] = report.losses
        log(f"train mesh {shape}: losses {report.losses}, "
            f"{time.perf_counter() - t0:.1f} s with compile")
        if shape == (2, 2):
            per_dev: dict = {}
            total = 0
            for leaf in jax.tree.leaves(params):
                total += leaf.nbytes
                for shard in leaf.addressable_shards:
                    per_dev[shard.device] = (per_dev.get(shard.device, 0)
                                             + shard.data.nbytes)
            log("params per device: " + ", ".join(
                f"{d.id}: {b / 1e9:.3f} GB" for d, b in sorted(
                    per_dev.items(), key=lambda kv: kv[0].id))
                + f" (total {total / 1e9:.3f} GB)")
            check(set(per_dev) == set(mesh.devices.flat),
                  "parameters do not span the four devices")
            check(max(per_dev.values()) < total,
                  "parameters are replicated, not sharded")
        del params, opt_state
    a, b = np.asarray(losses[(2, 2)]), np.asarray(losses[(1, 1)])
    rel = np.abs(a - b) / np.abs(b)
    log(f"loss 2x2 vs 1 chip: rel diff {np.array2string(rel, precision=6)} "
        f"(bound {LOSS_REL_TOL})")
    check(bool(np.all(np.isfinite(a)) and np.all(np.isfinite(b))),
          "non-finite loss")
    check(bool(np.all(rel <= LOSS_REL_TOL)), "losses disagree across meshes")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: sharded training only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    log = functools.partial(print, flush=True)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_arch
    from repro.core import dispatch
    from repro.hw import chip_spec

    log(f"device: {dev.device_kind}, {len(devices)} device(s), platform "
        f"{dev.platform}; peaks {chip_spec(dev.device_kind)}")
    log(f"compile cache: {enable_compile_cache()}")
    yi = get_arch("yi-6b")
    compiles = CompileCounter()
    t_start = time.perf_counter()
    try:
        if args.chips == 4:
            plan = TrainPlan()
            cfg = dataclasses.replace(yi, num_layers=plan.layers)
            log(f"model: {yi.name} widths, {plan.layers} of {yi.num_layers} "
                f"layers (depth cut so one chip holds weights and AdamW "
                f"state), {cfg.total_params() / 1e9:.2f} B params")
            train_compare(cfg, plan, args.seed, log)
        else:
            plan = ServePlan()
            cfg = dataclasses.replace(yi, num_layers=plan.layers)
            log(f"model: {yi.name}, {plan.layers} of {yi.num_layers} layers "
                f"(depth cut so bf16 weights + KV pool fit one chip), widths "
                f"d_model={cfg.d_model} heads={cfg.num_heads}/"
                f"{cfg.num_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
                f"vocab={cfg.vocab_size}, {cfg.total_params() / 1e9:.2f} B "
                f"params")
            trace = dispatch.DispatchTrace()
            t0 = time.perf_counter()
            model, params = init_model(cfg, args.seed)
            jax.block_until_ready(params)
            log(f"init: {time.perf_counter() - t0:.1f} s")
            with dispatch.use(trace=trace):
                t0 = time.perf_counter()
                done = serve(model, params, plan, args.seed, log)
                log(f"serve: {time.perf_counter() - t0:.1f} s with compile")
                log_trace(trace, log)
                target = next(r for r in done if len(r.prompt) == plan.check_len)
                t0 = time.perf_counter()
                check_logits(model, params, plan, target, log)
                log(f"logit check: {time.perf_counter() - t0:.1f} s")
            kernel_trace = dispatch.DispatchTrace()
            check_kernel(cfg, plan, args.seed, interpret=False,
                         trace=kernel_trace, log=log)
            log_trace(kernel_trace, log)
    finally:
        compiles.close()
    stats = dev.memory_stats() or {}
    log(f"compiles: {compiles.n} ({compiles.cache_hits} from the persistent "
        f"cache), {compiles.seconds:.1f} s compiling; total "
        f"{time.perf_counter() - t_start:.1f} s")
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
