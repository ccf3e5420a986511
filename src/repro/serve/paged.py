"""Paged KV cache: block pool, page allocator, block tables.

The dense serving cache reserves ``[slots, max_len]`` KV rows per slot at
admission — a short request strands almost its whole reservation, and the
engine's concurrency ceiling is ``pool_bytes / (max_len · bytes_per_token)``
regardless of how long requests actually run.  The paged cache instead
treats KV memory the way the paper treats compute: a pool of
runtime-(re)assignable regions.  Pages are the memory analogue of the
paper's partially-reconfigurable regions — a fixed-size physical resource
bound to a logical tenant at runtime and returned to the pool the moment
the tenant finishes — so admission is bounded by *actual* footprint, not by
the worst-case reservation.

Layout
------
Each KV cache leaf ``[L, B, Hkv, max_len, hd]`` of the dense engine becomes
a pool leaf ``[L, P, Hkv, page_size, hd]``: axis 1 indexes *pages* instead
of slots.  A per-slot block table ``[slots, max_len/page_size]`` maps
logical page indices to pool pages; one table is shared by every layer and
every leaf (all layers cache the same positions).  Page 0 is reserved as a
scratch ("trash") page: unmapped table entries point at it, so the fused
decode scan's masked dummy writes (finished slots keep absorbing writes at
their frozen position — see ``ServeEngine._fused_decode_fn``) land
somewhere harmless instead of corrupting a live page.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ledger import host_sync

TRASH_PAGE = 0


class PagePoolExhausted(RuntimeError):
    """A page allocation found the pool empty.

    Unreachable when admission runs with ``AdmissionPolicy.growth_reserve``
    = 1.0 (every admitted request's worst-case page count is accounted
    before admission); possible under optimistic overcommit (< 1.0), where
    the caller decided the projection risk was acceptable.
    """


@dataclasses.dataclass
class PageStats:
    total_pages: int                 # usable pages (scratch page excluded)
    free_pages: int
    allocated_pages: int
    high_water: int                  # max simultaneously allocated
    allocs: int
    frees: int
    quarantined: int = 0             # retired after a digest mismatch
    shared_pages: int = 0            # pages with refcount > 1 right now
    shares: int = 0                  # cumulative share() grants


class PageAllocator:
    """Free-list allocator over the global block pool, with refcounts.

    Page 0 is never handed out (the scratch page for masked writes).
    A page may be held by *several* owners at once (prefix sharing maps one
    physical page into many block tables): ``allocate`` mints a page with
    one owner, ``share`` adds an owner to an allocated page, and ``free``
    drops one owner's reference — the page returns to the free-list only
    when its last reference goes.  Double-free, foreign-free, and
    double-share are hard errors so serving bugs surface as exceptions,
    not silent corruption.
    """

    def __init__(self, num_pages: int) -> None:
        if num_pages < 2:
            raise ValueError(
                f"need >= 2 pages (1 scratch + 1 usable), got {num_pages}"
            )
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, TRASH_PAGE, -1))
        self._owners: dict[int, set[int]] = {}  # page -> owner uids
        self._quarantined: set[int] = set()     # retired (digest mismatch)
        self._refs_outstanding = 0
        self._high_water = 0
        self._allocs = 0
        self._frees = 0
        self._shares = 0

    @property
    def total_pages(self) -> int:
        # scratch page is not usable; quarantined pages left circulation
        return self.num_pages - 1 - len(self._quarantined)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocated_pages(self) -> int:
        return len(self._owners)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by more than one owner."""
        return sum(1 for owners in self._owners.values() if len(owners) > 1)

    def allocate(self, owner: int, n: int = 1) -> list[int]:
        """Take ``n`` pages for ``owner`` (a request uid). All-or-nothing."""
        if n > len(self._free):
            raise PagePoolExhausted(
                f"requested {n} pages, {len(self._free)} free "
                f"({self.allocated_pages}/{self.total_pages} allocated) — "
                "admission overcommitted (growth_reserve < 1.0)?"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._owners[p] = {owner}
        self._allocs += n
        self._refs_outstanding += n
        self._high_water = max(self._high_water, len(self._owners))
        return pages

    def share(self, page: int, owner: int) -> None:
        """Add ``owner`` as a reader of an already-allocated ``page``.

        The page must be live (allocated to at least one other owner) and
        ``owner`` must not already hold it — sharing a free, quarantined,
        or already-held page is a hard error.
        """
        if page == TRASH_PAGE:
            raise ValueError("cannot share the scratch page")
        owners = self._owners.get(page)
        if owners is None:
            state = "quarantined" if page in self._quarantined else "free"
            raise ValueError(f"cannot share {state} page {page}")
        if owner in owners:
            raise ValueError(f"request {owner} already holds page {page}")
        owners.add(owner)
        self._shares += 1
        self._refs_outstanding += 1

    def free(self, owner: int, pages: list[int]) -> list[int]:
        """Drop ``owner``'s reference on each of ``pages``; every page must
        be held by ``owner``.  Returns the pages whose *last* reference was
        dropped — i.e. the ones actually returned to the free-list (callers
        keyed on physical pages, like digest stamps, must only forget those).
        """
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("cannot free the scratch page")
            owners = self._owners.get(p)
            if owners is None:
                raise ValueError(f"double free of page {p}")
            if owner not in owners:
                raise ValueError(
                    f"page {p} belongs to request(s) {sorted(owners)}, "
                    f"not {owner}"
                )
        released = []
        for p in pages:
            owners = self._owners[p]
            owners.discard(owner)
            self._refs_outstanding -= 1
            if not owners:
                del self._owners[p]
                self._free.append(p)
                released.append(p)
        self._frees += len(pages)
        return released

    def pages_of(self, owner: int) -> list[int]:
        return [p for p, o in self._owners.items() if owner in o]

    def owner_of(self, page: int) -> int | None:
        """One holder uid of ``page`` (the smallest, for determinism), or
        None if free/quarantined.  Use :meth:`owners_of` for all readers."""
        owners = self._owners.get(page)
        return min(owners) if owners else None

    def owners_of(self, page: int) -> set[int]:
        """All holder uids of ``page`` (empty if free/quarantined)."""
        return set(self._owners.get(page, ()))

    def refcount(self, page: int) -> int:
        return len(self._owners.get(page, ()))

    def quarantine(self, page: int) -> None:
        """Retire ``page`` from circulation after a digest mismatch.

        The page must currently be free (detection paths park/release
        *every* reader first — a shared page only reaches refcount zero
        once all of them let go); it never returns to the free list, so the
        pool permanently shrinks by one page — the hardware-honest model of
        a block whose storage can no longer be trusted.
        """
        if page == TRASH_PAGE:
            raise ValueError("cannot quarantine the scratch page")
        owners = self._owners.get(page)
        if owners:
            raise ValueError(
                f"page {page} still belongs to request(s) {sorted(owners)}; "
                "release every reader before quarantining"
            )
        try:
            self._free.remove(page)
        except ValueError:
            raise ValueError(
                f"page {page} is not in the pool (already quarantined?)"
            ) from None
        self._quarantined.add(page)

    @property
    def quarantined_pages(self) -> int:
        return len(self._quarantined)

    def stats(self) -> PageStats:
        return PageStats(
            total_pages=self.total_pages,
            free_pages=self.free_pages,
            allocated_pages=self.allocated_pages,
            high_water=self._high_water,
            allocs=self._allocs,
            frees=self._frees,
            quarantined=len(self._quarantined),
            shared_pages=self.shared_pages,
            shares=self._shares,
        )

    def check_invariants(self) -> None:
        """free + allocated + quarantined must tile the pool, no aliasing,
        and references must conserve: every allocated page has >= 1 owner
        and the per-page owner sets sum to the outstanding-reference
        counter (allocate/share increments, free decrements)."""
        allocated = set(self._owners)
        free = set(self._free)
        assert not (allocated & free), f"aliased pages {allocated & free}"
        assert not (self._quarantined & allocated), \
            f"quarantined pages owned {self._quarantined & allocated}"
        assert not (self._quarantined & free), \
            f"quarantined pages free {self._quarantined & free}"
        assert TRASH_PAGE not in allocated and TRASH_PAGE not in free
        assert TRASH_PAGE not in self._quarantined
        union = allocated | free | self._quarantined
        expect = set(range(1, self.num_pages))
        assert union == expect, f"leaked pages {expect - union}"
        assert all(self._owners.values()), "allocated page with no owner"
        refs = sum(len(o) for o in self._owners.values())
        assert refs == self._refs_outstanding, (
            f"refcount leak: {refs} held vs {self._refs_outstanding} "
            "outstanding"
        )


# ---------------------------------------------------------------------------
# pool construction / prefill scatter (pure-jax helpers the engine jits)
# ---------------------------------------------------------------------------


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to store ``tokens`` KV rows."""
    return -(-tokens // page_size)


def build_pool(slot_cache_segments, num_pages: int, page_size: int):
    """Zeroed pool tree from one slot's prefill cache ``segments`` tree.

    Each leaf ``[L, 1, Hkv, T, hd]`` maps to ``[L, num_pages, Hkv,
    page_size, hd]``; non-KV leaves are rejected upstream by the engine's
    paged-support check.
    """
    def leaf(x):
        L, _, H, _, hd = x.shape
        return jnp.zeros((L, num_pages, H, page_size, hd), x.dtype)

    return jax.tree.map(leaf, slot_cache_segments)


def scatter_prefill(pool_segments, slot_segments, pages: jax.Array,
                    page_size: int):
    """Write one slot's prefill cache into its freshly mapped pages.

    ``slot_segments`` leaves are ``[L, 1, Hkv, T, hd]`` with T >= n·ps;
    ``pages`` is the [n] array of pool pages covering positions
    ``[0, n·ps)``.  Page tails beyond the prompt hold prefill values of pad
    positions — causally inert, masked by ``length`` at attention time.
    """
    n = pages.shape[0]

    def leaf(pool, one):
        L, _, H, T, hd = one.shape
        src = one[:, 0, :, : n * page_size]                   # [L,H,n*ps,hd]
        src = src.reshape(L, H, n, page_size, hd).transpose(0, 2, 1, 3, 4)
        return pool.at[:, pages].set(src.astype(pool.dtype))

    return jax.tree.map(leaf, pool_segments, slot_segments)


def scatter_chunk(pool_segments, slot_segments, table_row: jax.Array,
                  start: int, count: int, page_size: int):
    """Write one prefill chunk's rows ``[start, start+count)`` into the pool.

    Chunk-granular sibling of :func:`scatter_prefill`: the rows land in
    whatever pages ``table_row`` (the slot's full block-table row) maps
    their positions to, page-alignment-free — a chunk may straddle a page
    boundary or fill the middle of a page another chunk started.  Only real
    prompt rows are scattered; pad rows stay in staging (attention masks
    them by ``length``, exactly like the unchunked path's page tails).
    """
    pos = start + jnp.arange(count)
    pages = table_row[pos // page_size]                       # [count]
    offs = pos % page_size                                    # [count]

    def leaf(pool, one):
        src = one[:, 0, :, start:start + count]               # [L,H,count,hd]
        src = jnp.moveaxis(src, 2, 0)                         # [count,L,H,hd]
        return pool.at[:, pages, :, offs].set(src.astype(pool.dtype))

    return jax.tree.map(leaf, pool_segments, slot_segments)


def scatter_rows(slot_segments, saved, start_row: int, page_size: int):
    """Write a :func:`gather_pages` tree into a dense staging cache.

    Inverse of the page gather for the *staging* layout: ``saved`` leaves
    are ``[L, n, Hkv, page_size, hd]`` page stacks; they land as rows
    ``[start_row, start_row + n·page_size)`` of the ``[L, 1, Hkv, T, hd]``
    staging leaves.  This is how a shared prefix already resident in the
    pool seeds the suffix-only prefill: the chunked-prefill contract wants
    previous rows in the staging cache, and pool pages hold exactly the
    bytes those rows would contain.
    """
    def leaf(one, sv):
        L, n, H, ps, hd = sv.shape
        rows = jnp.asarray(sv, one.dtype).transpose(0, 2, 1, 3, 4)
        rows = rows.reshape(L, H, n * ps, hd)
        return one.at[:, 0, :, start_row:start_row + n * ps].set(rows)

    return jax.tree.map(leaf, slot_segments, saved)


# ---------------------------------------------------------------------------
# prefix sharing: page-granular prompt hashing + the shared-page index
# ---------------------------------------------------------------------------
#
# The paper's Table II `if_not_configured` hit is a tenant finding its
# kernel already resident and paying nothing for reconfiguration.  The KV
# analogue: a request finding its prompt prefix already paged in and paying
# nothing to prefill it.  Prefixes are hashed per *full* page of prompt
# tokens with a rolling digest, so equal keys mean equal token histories —
# and, because KV rows at position t depend only on tokens [0, t], equal
# token histories mean bitwise-equal page contents.


def prefix_page_keys(tokens, page_size: int,
                     max_pages: int | None = None) -> list[bytes]:
    """Rolling digest chain over full pages of ``tokens``.

    ``keys[i]`` commits to tokens ``[0, (i+1)·page_size)`` — key equality
    between two prompts implies their first ``i+1`` pages of KV are
    bitwise-identical.  Only *full* pages get keys: a trailing partial page
    is never shared (decode writes land there).
    """
    toks = np.asarray(tokens, np.int64)
    full = len(toks) // page_size
    if max_pages is not None:
        full = min(full, max_pages)
    keys: list[bytes] = []
    prev = b""
    for i in range(full):
        h = hashlib.blake2b(digest_size=16)
        h.update(prev)
        h.update(toks[i * page_size:(i + 1) * page_size].tobytes())
        prev = h.digest()
        keys.append(prev)
    return keys


class PrefixIndex:
    """Prefix-key → resident pool page map (the KV "hit-rate" table).

    Holds *no* references of its own: an entry is only valid while the
    page is allocated, and the engine drops entries the moment ``free``
    reports the page released (or it is quarantined).  ``publish`` is
    first-wins — once a key maps to a live page, later prefills of the
    same prefix attach to it rather than replacing it.
    """

    def __init__(self) -> None:
        self._by_key: dict[bytes, int] = {}
        self._by_page: dict[int, bytes] = {}

    def __len__(self) -> int:
        return len(self._by_key)

    def get(self, key: bytes) -> int | None:
        return self._by_key.get(key)

    def publish(self, key: bytes, page: int) -> bool:
        """Map ``key`` to ``page`` unless the key is already published.
        Returns True when the entry was added."""
        if key in self._by_key:
            return False
        old = self._by_page.get(page)
        if old is not None:            # page recycled under a new prefix
            del self._by_key[old]
        self._by_key[key] = page
        self._by_page[page] = key
        return True

    def drop_page(self, page: int) -> None:
        """Forget the entry backed by ``page`` (page released/quarantined)."""
        key = self._by_page.pop(page, None)
        if key is not None:
            del self._by_key[key]

    def pages(self) -> set[int]:
        return set(self._by_page)


# ---------------------------------------------------------------------------
# page snapshot save/restore (preemption's zero-recompute resume path)
# ---------------------------------------------------------------------------


def gather_pages(pool_segments, pages: np.ndarray, *, ledger=None):
    """Copy ``pages`` of every pool leaf to host memory, one host sync per
    leaf counted in ``ledger``.

    Returns a tree of numpy arrays ``[L, n, Hkv, page_size, hd]`` — the
    victim's KV exactly as it sits in the pool.  The copy is bit-preserving
    (device → host of the same dtype), which is what lets a snapshot resume
    keep the engine's bitwise-identity guarantee without recomputing
    anything.
    """
    idx = jnp.asarray(pages, jnp.int32)
    return host_sync(ledger,
                     jax.tree.map(lambda pool: pool[:, idx], pool_segments))


def restore_pages(pool_segments, saved, pages: np.ndarray):
    """Scatter a :func:`gather_pages` snapshot back into freshly mapped
    ``pages`` (the *physical* page ids may differ from the ones saved —
    the block table indirection is what makes that invisible)."""
    idx = jnp.asarray(pages, jnp.int32)
    return jax.tree.map(
        lambda pool, sv: pool.at[:, idx].set(jnp.asarray(sv, pool.dtype)),
        pool_segments, saved,
    )


def snapshot_bytes(saved) -> int:
    """Host bytes a :func:`gather_pages` snapshot holds while parked."""
    return sum(leaf.nbytes for leaf in jax.tree.leaves(saved))


# ---------------------------------------------------------------------------
# content digests: the integrity layer's ground truth
# ---------------------------------------------------------------------------
#
# A digest is stamped at a write boundary (prefill scatter, chunk scatter,
# decode page seal, arena store) and re-checked wherever the bytes are
# trusted again (decode reads, DMA completion, scrub).  blake2b-128 — fast
# in pure python-stdlib, collision-safe far beyond any pool size here.


def tree_digest(tree) -> bytes:
    """Content digest of a whole KV tree (a :func:`gather_pages` snapshot
    or any array pytree), leaf-order dependent like the tree itself."""
    h = hashlib.blake2b(digest_size=16)
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.digest()


def page_digest(pool_segments, page: int) -> bytes:
    """Content digest of one physical ``page`` across every pool leaf."""
    h = hashlib.blake2b(digest_size=16)
    for leaf in jax.tree.leaves(pool_segments):
        h.update(np.asarray(leaf[:, page]).tobytes())
    return h.digest()


def flip_page(pool_segments, page: int):
    """Pool tree with one byte of ``page`` flipped in the first leaf —
    the fault injector's model of a silent device-memory bit flip."""
    flipped = False

    def leaf(x):
        nonlocal flipped
        if flipped:
            return x
        flipped = True
        host = np.asarray(x[:, page]).copy()
        host.view(np.uint8).reshape(-1)[0] ^= 0xFF
        return x.at[:, page].set(jnp.asarray(host, x.dtype))

    return jax.tree.map(leaf, pool_segments)


def flip_tree(tree):
    """Copy of ``tree`` with one byte flipped in the first leaf — the
    injector's model of a DMA that completes but delivers wrong bytes."""
    flipped = False

    def leaf(x):
        nonlocal flipped
        host = np.asarray(x).copy()
        if not flipped:
            flipped = True
            host.view(np.uint8).reshape(-1)[0] ^= 0xFF
        return host

    return jax.tree.map(leaf, tree)


# ---------------------------------------------------------------------------
# host arena: the budgeted second tier of the page pool
# ---------------------------------------------------------------------------


class HostArenaExhausted(RuntimeError):
    """A snapshot store found the host arena past its byte budget.

    Only reachable when degradation is disabled (``SpillPolicy.allow_replay
    = False``): with replay allowed the engine demotes parked snapshots to
    re-prefill replay until the store fits, so the budget is a ceiling the
    arena never crosses rather than an error the caller sees.
    """


class HostArena:
    """Budgeted host-side tier for cold KV pages.

    The device pool (tier 0) holds hot pages; parked-request snapshots —
    and, by design, any future cold-page class (shared-prefix tails,
    beyond-window history) — spill D2H into this arena (tier 1).  Like the
    device :class:`PageAllocator` it is an explicit free-list over
    fixed-size blocks with tracked owners, so conservation is an assertable
    invariant rather than an accounting convention.  One block holds the
    bytes of one device page (``configure`` is called lazily once the
    engine knows its per-page byte size), which keeps the two tiers'
    accounting commensurable: N device pages spill into N host blocks.

    ``budget_bytes=None`` means unbounded (the pre-tiering behavior):
    blocks are minted on demand and the free-list stays exact, so the
    conservation invariants hold either way.  With a budget, a store that
    does not fit raises :class:`HostArenaExhausted`; callers degrade by
    demoting victims (see ``SpillPolicy``) before retrying.

    Entries are keyed by owner uid.  ``eviction_order()`` is store order,
    oldest first — the default victim scan for policies that do not rank
    by resume cost.
    """

    def __init__(self, budget_bytes: int | None = None) -> None:
        if budget_bytes is not None and budget_bytes < 0:
            raise ValueError(f"budget_bytes must be >= 0, got {budget_bytes}")
        self.budget_bytes = budget_bytes
        self.block_bytes: int | None = None      # set lazily by configure()
        self._total_blocks = 0
        self._free: list[int] = []
        self._owner: dict[int, int] = {}         # block -> owner uid
        self._blocks: dict[int, list[int]] = {}  # uid -> its blocks
        self._data: dict[int, Any] = {}          # uid -> snapshot tree
        self._nbytes: dict[int, int] = {}        # uid -> actual bytes stored
        self._digest: dict[int, bytes] = {}      # uid -> store-time digest
        self._order: list[int] = []              # uids in store order
        self.peak_bytes = 0
        self.stores = 0
        self.discards = 0

    # -- sizing ------------------------------------------------------------

    def configure(self, block_bytes: int) -> None:
        """Fix the block size (bytes of one device page).  Idempotent; a
        conflicting re-configure is a hard error — resizing live blocks
        would silently break the free-list ↔ budget correspondence."""
        if self.block_bytes is not None:
            if block_bytes != self.block_bytes:
                raise ValueError(
                    f"arena already configured with block_bytes="
                    f"{self.block_bytes}, got {block_bytes}"
                )
            return
        if block_bytes < 1:
            raise ValueError(f"block_bytes must be >= 1, got {block_bytes}")
        self.block_bytes = block_bytes
        if self.budget_bytes is not None:
            self._total_blocks = self.budget_bytes // block_bytes
            self._free = list(range(self._total_blocks - 1, -1, -1))

    def blocks_for(self, nbytes: int) -> int:
        """Blocks needed to hold ``nbytes`` (at least one)."""
        if self.block_bytes is None:
            raise RuntimeError("arena not configured (block_bytes unset)")
        return max(1, -(-nbytes // self.block_bytes))

    # -- accounting --------------------------------------------------------

    @property
    def total_blocks(self) -> int:
        return self._total_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._owner)

    @property
    def used_bytes(self) -> int:
        """Actual snapshot bytes resident (<= used_blocks · block_bytes)."""
        return sum(self._nbytes.values())

    def fits(self, nbytes: int) -> bool:
        """Would a store of ``nbytes`` succeed right now?"""
        if self.budget_bytes is None:
            return True
        return self.blocks_for(nbytes) <= len(self._free)

    def can_ever_fit(self, nbytes: int) -> bool:
        """Would ``nbytes`` fit into an *empty* arena?  False means no
        amount of demotion helps — the entry must go straight to replay."""
        if self.budget_bytes is None:
            return True
        return self.blocks_for(nbytes) <= self._total_blocks

    # -- store / load / discard --------------------------------------------

    def holds(self, uid: int) -> bool:
        return uid in self._data

    def bytes_of(self, uid: int) -> int:
        return self._nbytes[uid]

    def entries(self) -> list[int]:
        """Resident uids in eviction order (oldest store first)."""
        return list(self._order)

    def store(self, uid: int, data: Any, nbytes: int,
              digest: bytes | None = None) -> None:
        """Park ``data`` (a :func:`gather_pages` tree) under ``uid``.

        ``digest`` stamps the block's content at its write boundary — the
        engine passes the *pre-transfer* digest, so corruption anywhere
        downstream (the D2H DMA, the arena's own storage) is caught by
        :meth:`verify` or by the refill-wait payload check.  ``None`` skips
        stamping (the integrity layer is off); ``verify`` then always
        passes."""
        if uid in self._data:
            raise ValueError(f"uid {uid} already holds an arena entry")
        need = self.blocks_for(nbytes)
        if self.budget_bytes is None:
            while len(self._free) < need:       # unbounded: mint blocks
                self._free.append(self._total_blocks)
                self._total_blocks += 1
        elif need > len(self._free):
            raise HostArenaExhausted(
                f"store of {nbytes} B ({need} blocks) over budget: "
                f"{len(self._free)}/{self._total_blocks} blocks free, "
                f"budget {self.budget_bytes} B"
            )
        blocks = [self._free.pop() for _ in range(need)]
        for b in blocks:
            self._owner[b] = uid
        self._blocks[uid] = blocks
        self._data[uid] = data
        self._nbytes[uid] = nbytes
        if digest is not None:
            self._digest[uid] = digest
        self._order.append(uid)
        self.stores += 1
        self.peak_bytes = max(self.peak_bytes, self.used_bytes)

    def load(self, uid: int) -> Any:
        """Peek the stored snapshot without freeing its blocks."""
        return self._data[uid]

    def digest_of(self, uid: int) -> bytes | None:
        """Store-time content digest of ``uid``'s block (None if the
        integrity layer never stamped one)."""
        return self._digest.get(uid)

    def verify(self, uid: int) -> bool:
        """Re-hash ``uid``'s stored tree against its store-time digest —
        the scrubber's arena probe.  True when unstamped or dataless."""
        expect = self._digest.get(uid)
        data = self._data.get(uid)
        if expect is None or data is None:
            return True
        return tree_digest(data) == expect

    def corrupt(self, uid: int) -> None:
        """Flip one byte of ``uid``'s stored snapshot (fault injection:
        host memory rotting under a parked block).  The store-time digest
        is untouched, so :meth:`verify` and the refill payload check both
        see the mismatch.  Device-backed leaves are immutable, so the
        flipped leaf is rebuilt as a host copy — byte-identical except for
        the one flipped bit."""
        data = self._data[uid]
        if data is None:
            raise ValueError(f"uid {uid} holds no payload to corrupt")
        leaves, treedef = jax.tree.flatten(data)
        host = np.array(leaves[0])
        host.view(np.uint8).reshape(-1)[0] ^= 0xFF
        leaves[0] = host
        self._data[uid] = jax.tree.unflatten(treedef, leaves)

    def discard(self, uid: int) -> int:
        """Drop ``uid``'s entry, return its blocks to the free-list.

        Returns the bytes freed — what a demotion gives back to the
        budget, and what the ledger prices the demotion at.
        """
        if uid not in self._data:
            raise ValueError(f"uid {uid} holds no arena entry")
        for b in self._blocks.pop(uid):
            del self._owner[b]
            self._free.append(b)
        del self._data[uid]
        self._digest.pop(uid, None)
        self._order.remove(uid)
        self.discards += 1
        return self._nbytes.pop(uid)

    def take(self, uid: int) -> Any:
        """Load + discard in one step (the refill-complete path)."""
        data = self._data[uid]
        self.discard(uid)
        return data

    # -- invariants --------------------------------------------------------

    def check_invariants(self) -> None:
        """free + owned must tile [0, total_blocks) exactly; per-entry
        block counts must match the byte accounting; budget never crossed."""
        owned = set(self._owner)
        free = set(self._free)
        assert not (owned & free), f"aliased blocks {owned & free}"
        union = owned | free
        expect = set(range(self._total_blocks))
        assert union == expect, (
            f"leaked blocks {expect - union} / phantom {union - expect}"
        )
        assert set(self._data) == set(self._blocks) == set(self._nbytes)
        assert set(self._digest) <= set(self._data), "orphaned digests"
        assert set(self._order) == set(self._data)
        assert len(self._order) == len(self._data)
        for uid, blocks in self._blocks.items():
            assert len(blocks) == self.blocks_for(self._nbytes[uid])
            assert all(self._owner[b] == uid for b in blocks)
        if self.budget_bytes is not None:
            assert self.used_blocks * (self.block_bytes or 0) \
                <= self.budget_bytes
            assert self.used_bytes <= self.budget_bytes


#: cache leaves with a position axis (the ones a page actually stores rows
#: of); recurrent state (ssm_state, conv_tail) has no per-token capacity
#: and is skipped by the memory accounting.
_TIME_INDEXED_KEYS = frozenset({"k", "v", "ckv", "krope", "mem_k", "mem_v"})


def pool_token_bytes(segments) -> int:
    """Bytes per cached token position across all time-indexed leaves.

    ``reserved = mapped_pages · page_size · pool_token_bytes`` is the
    engine's live KV reservation; the same per-token figure prices the
    dense engine's ``slots · max_len`` reservation, so the Table I-style
    utilization split compares like with like.  Leaves are [L, pages|B, H,
    ps|T, hd]: per-token bytes drop the two middle capacity axes.
    """
    import jax.tree_util as jtu

    total = 0

    def visit(path, leaf):
        nonlocal total
        last = path[-1]
        key = last.key if hasattr(last, "key") else str(last)
        if key in _TIME_INDEXED_KEYS and leaf.ndim >= 4:
            # [L, pages|B, ..., T, ...]: drop the capacity axes (1 and -2)
            per_token = int(np.prod(leaf.shape)) // (
                leaf.shape[1] * leaf.shape[-2]
            )
            total += per_token * leaf.dtype.itemsize

    jtu.tree_map_with_path(visit, segments)
    return total
