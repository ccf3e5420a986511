"""Drives ``ServeEngine`` through its public calls: warm-up, the measured
window, and the drain that follows it.

Everything runs in one thread: ``submit`` and ``step`` alternate.  The
driver's own calls are wrapped in ``jax.profiler.TraceAnnotation`` spans
(``bench.submit``, ``engine.step``, ``bench.idle``), so that a traced run
can say what the host was doing while the device sat idle.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import loadgen


@dataclasses.dataclass
class Record:
    """What happened to one request of the window."""

    spec: loadgen.RequestSpec
    due: float                         # engine clock
    submitted: float                   # engine clock
    uid: int
    first_token: float | None = None
    finish: float | None = None
    generated: list[int] | None = None
    admit: float | None = None         # engine clock: a slot took it

    @property
    def done(self) -> bool:
        return self.finish is not None


@dataclasses.dataclass
class Window:
    """The measured window and what it served."""

    start: float                       # engine clock
    seconds: float
    records: list[Record]
    drain_end: float
    steps: int                         # engine steps in the window
    drain_steps: int = 0               # and in the drain after it


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------


class Warmup:
    """Makes the engine compile, before the window, every program and eager
    operation the cell's traffic will use.

    It serves requests of every size in the mix's multiset through
    ``submit`` and ``step``, in each way the window can serve them: with
    nothing attached, and with the request's system prompt attached from a
    live holder.  So the engine alone decides which shapes it runs.  Then a
    lone request for each fused decode depth.  ``ledger`` is the engine's
    ledger, a ``bench.run`` ``EngineLog``: it says when a request's first
    token came and whether each admitted request found its prefix.
    """

    def __init__(self, engine, ledger, rows: list[tuple[int, int, int, int]],
                 vocab: int, seed: int, fusion: int):
        self.engine, self.ledger, self.rows = engine, ledger, rows
        self.vocab, self.fusion = vocab, fusion
        self.done: set[int] = set()           # uids the engine completed
        self.steps, self.max_steps = 0, 3_000
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))

    def _tokens(self, n: int) -> list[int]:
        return self.rng.integers(loadgen.FIRST_TOKEN, self.vocab, n).tolist()

    def _step(self) -> None:
        self.done |= {r.uid for r in self.engine.step()}
        self.steps += 1
        if self.steps > self.max_steps:
            raise RuntimeError(f"warm-up did not finish in {self.max_steps} steps")

    def _serve(self, prompts: list[list[int]], max_new: int) -> list[int]:
        uids = [self.engine.submit(p, max_new_tokens=max_new) for p in prompts]
        while set(uids) - self.done:
            self._step()
        return uids

    def unshared(self) -> int:
        """Every prompt length of the mix, with fresh tokens: nothing is
        resident to attach."""
        lengths = sorted({sl + u for _, sl, u, _ in self.rows})
        self._serve([self._tokens(n) for n in lengths], 2)
        return len(lengths)

    def shared(self, holder_tokens: int = 64) -> int:
        """Every user turn of each system prompt's group, attached to that
        prompt while a holder of it lives.  A turn that found nothing
        resident (its holder ended before it was admitted) is served again
        under a new holder with a longer budget."""
        served = 0
        groups: dict[int, tuple[int, set[int]]] = {}
        for g, sl, u, _ in self.rows:
            if g >= 0:
                groups.setdefault(g, (sl, set()))[1].add(u)
        for g, (sl, turns) in sorted(groups.items()):
            system, pending, budget = self._tokens(sl), sorted(turns), holder_tokens
            while pending:
                if budget > 8 * holder_tokens:
                    raise RuntimeError(f"system prompt {g}: {len(pending)} turns "
                                       "never found it resident")
                holder = self.engine.submit(system + self._tokens(pending[0]),
                                            max_new_tokens=budget)
                while holder not in self.ledger.first_token and holder not in self.done:
                    self._step()
                n0 = len(self.ledger.lookups)
                self._serve([system + self._tokens(u) for u in pending], 2)
                hits = [hit for hit, _ in self.ledger.lookups[n0:n0 + len(pending)]]
                served += len(pending) + 1
                pending = [u for u, hit in zip(pending, hits) if not hit]
                budget *= 2
                while holder not in self.done:
                    self._step()
        return served

    def decode_depths(self) -> int:
        """Every fused decode depth: a lone request whose budget leaves
        exactly ``k`` tokens after its first."""
        n = min(sl + u for _, sl, u, _ in self.rows)
        for k in range(1, self.fusion + 1):
            self._serve([self._tokens(n)], k + 1)
        return self.fusion


def chunk_rows(rows: list[tuple[int, int, int, int]], page_size: int,
               chunk: int) -> set[int]:
    """Row counts of the prefill chunks the mix's prompts need, with and
    without their system prompt's full pages attached: each prompt is
    prefilled from its first unattached row in chunks of ``chunk`` rows."""
    out = set()
    for _, sl, u, _ in rows:
        for start in {0, sl // page_size * page_size}:
            rest = sl + u - start
            out |= {chunk} if rest >= chunk else set()
            out |= {rest % chunk} - {0}
    return out


def precompile_pool_scatters(engine, model, counts: set[int], page_size: int,
                             max_len: int, threads: int = 4) -> float:
    """Compile the engine's eager pool scatter (``repro.serve.paged
    .scatter_chunk``) for each chunk row count in ``counts``, in
    ``threads`` threads, on a pool of the engine's shape made before the
    engine makes its own.  The program runs that scatter eagerly, and
    compiles it once per row count; this only does that compiling in
    parallel.  The warm-up's serving still covers every count the engine
    really uses.  Returns the seconds taken."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp

    from repro.serve import paged

    t0 = time.perf_counter()
    staging = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                           model.cache_specs(1, max_len)["segments"])
    pool = paged.build_pool(staging, engine.pool_pages, page_size)
    table = jnp.arange(max_len // page_size, dtype=jnp.int32) + 1

    def one(count: int) -> None:
        jax.block_until_ready(paged.scatter_chunk(pool, staging, table, 0, count,
                                                  page_size))

    with concurrent.futures.ThreadPoolExecutor(threads) as ex:
        list(ex.map(one, sorted(counts)))
    del pool, staging
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


def _submit(engine, spec, due: float, records: dict) -> None:
    with TraceAnnotation("bench.submit"):
        now = engine.clock.now()
        uid = engine.submit(spec.prompt.tolist(), max_new_tokens=spec.max_new_tokens,
                            arrival_t=due)
    records[uid] = Record(spec, due, now, uid)


def _step(engine, records: dict) -> list:
    with TraceAnnotation("engine.step"):
        done = engine.step()
    for r in done:
        rec = records.get(r.uid)
        if rec is not None:
            rec.first_token, rec.finish = r.first_token_t, r.finish_t
            rec.admit = r.admit_t
            rec.generated = list(r.generated)
    return done


def _drain(engine, records: dict, until: float) -> tuple[float, int]:
    """Step until every record is done or ``until``; the engine clock at
    the end and the steps taken."""
    steps = 0
    while any(not r.done for r in records.values()):
        if engine.clock.now() >= until:
            break
        _step(engine, records)
        steps += 1
    return engine.clock.now(), steps


def run_open(engine, specs: list, seconds: float, drain_s: float) -> Window:
    """Open loop: each request is submitted when it falls due, whatever
    the engine is doing; its latency counts from its due time."""
    clock = engine.clock
    records: dict[int, Record] = {}
    t0 = clock.now()
    end = t0 + seconds
    i, steps = 0, 0
    while True:
        now = clock.now()
        if now >= end:
            break
        while i < len(specs) and t0 + specs[i].due <= now:
            _submit(engine, specs[i], t0 + specs[i].due, records)
            i += 1
        if any(not r.done for r in records.values()):
            _step(engine, records)
            steps += 1
        else:
            nxt = t0 + specs[i].due if i < len(specs) else end
            with TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(nxt, end) - clock.now()))
    while i < len(specs) and specs[i].due < seconds:   # due before the close
        _submit(engine, specs[i], t0 + specs[i].due, records)
        i += 1
    drain_end, drained = _drain(engine, records, end + drain_s)
    return Window(t0, seconds, list(records.values()), drain_end, steps, drained)


def run_closed(engine, specs: list, clients: int, seconds: float,
               drain_s: float) -> Window:
    """Closed loop: ``clients`` clients, each submitting its next request
    as soon as its last one completes."""
    clock = engine.clock
    queues = [[s for s in specs if s.client == c] for c in range(clients)]
    records: dict[int, Record] = {}
    t0 = clock.now()
    end = t0 + seconds
    for q in queues:
        _submit(engine, q.pop(0), t0, records)
    steps = 0
    while clock.now() < end:
        for r in _step(engine, records):
            rec = records.get(r.uid)
            q = queues[rec.spec.client]
            if q and clock.now() < end:
                _submit(engine, q.pop(0), clock.now(), records)
        steps += 1
    drain_end, drained = _drain(engine, records, end + drain_s)
    return Window(t0, seconds, list(records.values()), drain_end, steps, drained)
