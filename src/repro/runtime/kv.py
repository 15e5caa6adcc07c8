"""KV-cache management for the continuous-batching engine.

The serve engine's scale bottleneck is its memory path: PR 7 gave every
slot a full ``max_seq`` dense cache and prefilled whole prompts in one
launch, stalling the decode loop.  This module extracts that state handling
behind :class:`KVCacheManager` with two backends:

* :class:`DenseKV` — the original layout: one stacked batch-1
  decode-state pytree per slot, whole-prompt prefill, one vmapped
  ``decode_slots`` launch, token-bit-identical to the pre-refactor
  engine (a transformer's rows body attends what ``decode_step`` would,
  through the same attention body).
* :class:`PagedKV` — a single global page pool ``[L, pages, page_tokens,
  Hkv, hd]`` with per-slot block tables.  On a TPU a decode launch attends
  each slot's pages in place, up to its length, through the paged
  attention kernel (``decode_body`` ``"pages"``).  Elsewhere it gathers
  each slot's pages into the *same contiguous layout the dense path
  decodes*, runs the identical decode math (``"rows"``) — so paged tokens
  are bit-identical to dense there, the oracle of the kernel path.  Either
  way the new rows are scattered back.  Pages holding a fully-prefilled
  prompt prefix are content-addressed (hash chain over page tokens) and
  shared across requests with the same prefix: a prefix hit skips those
  prefill tokens entirely, which is what makes the per-request command
  footprint (prefill doorbells, DMA payload bytes) sublinear in
  shared-prefix traffic.  Pool exhaustion evicts with ``reason="kv_pages"``
  (the dense ``kv_overrun`` cap semantics are preserved by the engine in
  both backends).

Both backends support **chunked prefill**: prompts longer than
``prefill_chunk`` are advanced one bounded ``prefill_extend`` launch at a
time (``serve.prefill_chunk`` spans), interleaved by the engine with decode
iterations so long prompts no longer stall active slots.  Chunked prefill
is bit-identical to whole-prompt prefill (masked-out future cache positions
contribute exactly-zero softmax weight; see ``models.attention``).

Page 0 of the pool is a reserved scratch page: free or still-prefilling
slots point every block-table row at it with length 0, so the vmapped
decode launch stays total and shape-stable — exactly the property the dense
backend gets from keeping a well-formed state in every slot.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.shapes import kv_geometry
from ..models.attention import gather_block_table, scatter_block_rows

if TYPE_CHECKING:                                    # pragma: no cover
    from .scheduler import RequestTicket
    from .server import Server

__all__ = ["KVCacheManager", "DenseKV", "PagedKV", "make_kv", "KV_BACKENDS"]

KV_BACKENDS = ("dense", "paged")


def _decode_body(model) -> str:
    """Which per-slot decode body a model gets: ``"rows"`` where it offers
    ``decode_rows`` (the transformer family: the T token steps read the
    cache and carry only the launch's new rows), else ``"carry"`` (the T
    steps carry the whole decode state)."""
    return "rows" if hasattr(model, "decode_rows") else "carry"


def _attends_pages_in_place(pool: jax.Array) -> bool:
    """Whether a paged decode launch over ``pool`` reads the pages in place
    through the paged attention kernel: on a TPU, where the kernel
    compiles.  Elsewhere the launch gathers the block tables and runs
    ``decode_rows``, the bit-identical twin of the dense path."""
    if isinstance(pool, jax.core.Tracer):     # built abstractly
        return jax.default_backend() == "tpu"
    return all(d.platform == "tpu" for d in pool.devices())


def _decode_slot_fn(model, T: int):
    """The dense per-slot decode body: T greedy steps, one launch.

    With ``decode_rows`` it is the body the paged launch runs too, the
    rows written back with one ``dynamic_update_slice``, whose start
    clamps to ``S - T`` exactly as the paged scatter's does — which is
    what makes the two backends' token streams bit-identical.
    """
    if _decode_body(model) == "rows":
        def decode_rows_slot(params, state, tok):
            length = state["length"]
            toks, nxt, rk, rv = model.decode_rows(
                params, state["k"], state["v"], length, tok, T)
            at = (0, 0, length, 0, 0)
            k = jax.lax.dynamic_update_slice(state["k"], rk[:, None], at)
            v = jax.lax.dynamic_update_slice(state["v"], rv[:, None], at)
            return {"k": k, "v": v, "length": length + T}, toks, nxt

        return decode_rows_slot

    def decode_slot(params, state, tok):             # state: batch-1 pytree
        def body(carry, _):
            st, t = carry
            st, logits = model.decode_step(params, st, t)
            nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(t.dtype)
            return (st, nxt), nxt[0, 0]
        (state, nxt), toks = jax.lax.scan(
            body, (state, tok), None, length=T)
        return state, toks, nxt                      # [T], [1, 1]

    return decode_slot


class KVCacheManager:
    """Backend interface the engine schedules against.

    Lifecycle per request: :meth:`begin` claims resources for a slot
    (returns False only on unrecoverable page exhaustion -> the engine
    evicts with ``reason="kv_pages"``); :meth:`prefill_step` advances the
    slot's prefill by at most one launch and returns the first generated
    token once the prompt is fully in cache; :meth:`reserve_decode` grows
    per-slot capacity ahead of a decode launch (returning victims when the
    pool cannot); :meth:`decode` runs the one vmapped launch over all
    slots; :meth:`release` returns the slot's memory.
    """

    name = "none"
    chunk = 0                    # prefill_chunk knob (0 = whole-prompt)

    def begin(self, slot: int, tix: "RequestTicket") -> bool:
        raise NotImplementedError

    def prefill_step(self, slot: int) -> Optional[int]:
        raise NotImplementedError

    def reserve_decode(self, slots: List[int]) -> List[int]:
        return []

    def decode(self) -> np.ndarray:
        raise NotImplementedError

    def release(self, slot: int) -> None:
        pass

    def stats(self) -> Dict[str, Any]:
        raise NotImplementedError


class _LaunchCounters:
    """Shared launch/byte accounting (feeds loadtest --json and BENCH)."""

    def __init__(self, decode_body: str) -> None:
        self.decode_body = decode_body   # body chosen at construction
        self.decode_launches = 0
        self.prefill_launches = 0        # all prefill/extend launches
        self.prefill_chunk_launches = 0  # the subset that were chunk ticks
        self.prefill_tokens = 0          # prompt tokens actually pushed
        self.chunked_prompts = 0         # prompts that needed >1 launch
        # query-key pairs of the extend launches: every one the attention
        # scores (each query against the whole cache buffer) and those the
        # causal mask keeps (each query against itself and what precedes it)
        self.extend_scored_positions = 0
        self.extend_live_positions = 0
        # pages of the paged decode launches: those the attention reads
        # and those the active slots' block tables hold
        self.decode_pages_read = 0
        self.decode_pages_table = 0

    def count_extend(self, start: int, size: int, buffer: int
                     ) -> Dict[str, int]:
        """Count one extend launch of ``size`` prompt positions from
        ``start`` against a cache of ``buffer`` positions; returns the
        launch's ``scored`` and ``live`` pairs, for its span."""
        scored = size * buffer
        live = size * start + size * (size + 1) // 2
        self.extend_scored_positions += scored
        self.extend_live_positions += live
        return {"scored": scored, "live": live}

    @property
    def prefill_payload_bytes(self) -> int:
        return 4 * self.prefill_tokens

    def to_dict(self) -> Dict[str, Any]:
        return {
            "decode_body": self.decode_body,
            "decode_launches": self.decode_launches,
            "prefill_launches": self.prefill_launches,
            "prefill_chunk_launches": self.prefill_chunk_launches,
            "prefill_tokens": self.prefill_tokens,
            "prefill_payload_bytes": self.prefill_payload_bytes,
            "chunked_prompts": self.chunked_prompts,
            "extend_scored_positions": self.extend_scored_positions,
            "extend_live_positions": self.extend_live_positions,
            "decode_pages_read": self.decode_pages_read,
            "decode_pages_table": self.decode_pages_table,
        }


def _require_extend(model, why: str) -> None:
    if not hasattr(model, "prefill_extend"):
        raise ValueError(
            f"{why} requires a model with a prefill_extend() decode-state "
            f"extension (transformer-family); {type(model).__name__} has "
            f"none — use kv='dense' with prefill_chunk=0")


class DenseKV(KVCacheManager):
    """Today's layout behind the manager interface (bit-identical refactor).

    Slot state and install scatter are the jitted functions the engine
    built inline before the refactor; the vmapped ``decode_slots`` launch
    runs :func:`_decode_slot_fn`.
    Chunked prefill stages chunks in a private batch-1 state and installs it
    on completion, so the stacked slot states see exactly one update per
    admission either way.
    """

    name = "dense"

    def __init__(self, engine: "Server", prefill_chunk: int = 0) -> None:
        self.engine = engine
        self.chunk = max(0, int(prefill_chunk))
        if self.chunk:
            _require_extend(engine.model, "chunked prefill")
        one = engine.model.init_decode_state(1, engine.max_seq)
        self._slots = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * engine.B), one)
        self._nxt = jnp.zeros((engine.B, 1, 1), jnp.int32)
        self._decode_slots = engine.tracker.wrap(
            jax.jit(jax.vmap(_decode_slot_fn(engine.model, engine.T),
                             in_axes=(None, 0, 0))),
            "decode_slots")
        # scatter one admitted request's prefilled state into its slot
        self._install = jax.jit(
            lambda full, part, i: jax.tree_util.tree_map(
                lambda f, o: jax.lax.dynamic_update_index_in_dim(f, o, i, 0),
                full, part))
        self._extend = None
        if self.chunk:
            self._extend = engine.tracker.wrap(
                jax.jit(engine.model.prefill_extend), "prefill_extend")
        self._pending: Dict[int, Dict[str, Any]] = {}
        self.counters = _LaunchCounters(_decode_body(engine.model))

    # -- prefill -----------------------------------------------------------
    def begin(self, slot: int, tix: "RequestTicket") -> bool:
        prompt = np.asarray(tix.request.prompt, np.int32)
        chunked = bool(self.chunk) and len(prompt) > self.chunk
        self._pending[slot] = {"tix": tix, "prompt": prompt, "pos": 0,
                               "chunked": chunked, "state": None}
        if chunked:
            self.counters.chunked_prompts += 1
        return True

    def prefill_step(self, slot: int) -> Optional[int]:
        p = self._pending[slot]
        tix, prompt = p["tix"], p["prompt"]
        eng = self.engine
        if not p["chunked"]:
            with eng.session.span("serve.prefill", uid=tix.uid,
                                  prompt_len=int(len(prompt))):
                state, logits = eng._prefill(eng.params, jnp.asarray(
                    prompt[None, :]))
            self.counters.prefill_launches += 1
            self.counters.prefill_tokens += len(prompt)
            tix.n_prefill_launches += 1
            return self._complete(slot, state, logits)
        if p["state"] is None:
            p["state"] = eng.model.init_decode_state(1, eng.max_seq)
        pos = p["pos"]
        c = min(self.chunk, len(prompt) - pos)
        pairs = self.counters.count_extend(pos, c, eng.max_seq)
        with eng.session.span("serve.prefill_chunk", uid=tix.uid, start=pos,
                              size=c, prompt_len=int(len(prompt)), **pairs):
            p["state"], logits = self._extend(
                eng.params, p["state"], jnp.asarray(prompt[None, pos:pos + c]))
        p["pos"] = pos + c
        self.counters.prefill_launches += 1
        self.counters.prefill_chunk_launches += 1
        self.counters.prefill_tokens += c
        tix.n_prefill_launches += 1
        if p["pos"] < len(prompt):
            return None
        return self._complete(slot, p["state"], logits)

    def _complete(self, slot: int, state, logits) -> int:
        with self.engine.session.span("serve.prefill.readback"):
            tok0 = int(jnp.argmax(logits[0, -1, :]))
        self._slots = self._install(self._slots, state, np.int32(slot))
        self._nxt = self._nxt.at[slot, 0, 0].set(tok0)
        del self._pending[slot]
        return tok0

    # -- decode ------------------------------------------------------------
    def decode(self) -> np.ndarray:
        self._slots, toks, self._nxt = self._decode_slots(
            self.engine.params, self._slots, self._nxt)
        self.counters.decode_launches += 1
        with self.engine.session.span("serve.decode.readback"):
            return np.asarray(toks)                  # [B, T] host sync

    def release(self, slot: int) -> None:
        self._pending.pop(slot, None)

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"backend": self.name,
                               "prefill_chunk": self.chunk}
        out.update(self.counters.to_dict())
        return out


def _hash_page(parent: bytes, tokens: np.ndarray) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(parent)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


class PagedKV(KVCacheManager):
    """Fixed-size pages + block tables + shared-prefix page reuse.

    Geometry: pool ``[L, 1 + pages, page_tokens, Hkv, hd]`` (page 0 is
    scratch), block tables ``[B, max_seq // page_tokens]`` host-side.  Pages
    whose span lies entirely inside the *prompt* (decode never writes them:
    the first decode write lands at position ``prompt_len``) are registered
    under a content hash chain once prefilled, and later requests sharing
    that prefix attach to them instead of re-prefilling — always leaving at
    least the final prompt token to prefill so the first output token's
    logits exist.

    Allocation: free list first, then reclaim of the least-recently-freed
    cached (refcount-0 but registered) page.  When both are empty the
    requester loses: ``begin`` returns False / ``reserve_decode`` reports
    the slot as a victim, and the engine evicts it with
    ``reason="kv_pages"``.
    """

    name = "paged"

    def __init__(self, engine: "Server", page_tokens: int = 16,
                 pages: Optional[int] = None,
                 prefill_chunk: int = 0) -> None:
        _require_extend(engine.model, "kv='paged'")
        self.engine = engine
        self.pt = int(page_tokens)
        self.chunk = max(0, int(prefill_chunk))
        self.n_blk, default_pages = kv_geometry(
            engine.max_seq, self.pt, engine.B)
        self.pages = int(pages) if pages is not None else default_pages
        if self.pages < self.n_blk:
            raise ValueError(
                f"kv_pages={self.pages} cannot hold even one full slot "
                f"({self.n_blk} pages of {self.pt} tokens)")
        cfg, model = engine.cfg, engine.model
        from ..models.layers import dtype_of
        P = 1 + self.pages                            # + scratch page 0
        shape = (cfg.n_layers, P, self.pt, cfg.n_kv_heads, cfg.hd)
        self.k_pool = jnp.zeros(shape, dtype_of(cfg))
        self.v_pool = jnp.zeros(shape, dtype_of(cfg))
        self.page_bytes = int(2 * np.prod(shape[2:]) * cfg.n_layers
                              * self.k_pool.dtype.itemsize)

        B = engine.B
        self.tables = np.zeros((B, self.n_blk), np.int32)   # 0 = scratch
        self.n_rows = np.zeros(B, np.int32)           # valid table rows
        self.lengths = np.zeros(B, np.int32)          # 0 until installed
        self.ready = np.zeros(B, bool)                # prefill complete
        self._nxt = jnp.zeros((B, 1, 1), jnp.int32)

        self._free: List[int] = list(range(P - 1, 0, -1))   # pop() -> 1,2,..
        self._ref = np.zeros(P, np.int64)
        self._key_of: Dict[int, bytes] = {}           # page -> content key
        self._page_of: Dict[bytes, int] = {}          # content key -> page
        self._cached: "OrderedDict[int, None]" = OrderedDict()  # ref==0 LRU
        self._chain: Dict[int, List[bytes]] = {}      # slot -> page keys
        self._pending: Dict[int, Dict[str, Any]] = {}

        self.in_place = _attends_pages_in_place(self.k_pool)
        self.counters = _LaunchCounters("pages" if self.in_place else "rows")
        self.pages_allocated = 0                      # cumulative fresh
        self.pages_reused = 0                         # prefix-hit attaches
        self.prefix_hits = 0                          # requests that hit
        self.prefix_hit_tokens = 0
        self.pages_peak = 0

        T, S = engine.T, engine.max_seq
        L, Hk, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd

        # The named scopes label the programs' ops in a profiler trace:
        # the table gather, the model, the scatter back.  The scatter's
        # start clamps exactly like the dense dynamic_update_slice when a
        # finishing slot overruns.  Rows come back [L, B, T, Hkv, hd].
        def gathered_rows(params, k_pool, v_pool, tables, lengths, nxt):
            def slot_fn(table, length, tok):
                with jax.named_scope("kv_gather"):
                    k = gather_block_table(k_pool, table)
                    v = gather_block_table(v_pool, table)
                with jax.named_scope("decode_slot"):
                    toks, tok, rk, rv = model.decode_rows(
                        params, k, v, length, tok, T)
                return toks, tok, rk, rv

            toks, nxts, rows_k, rows_v = jax.vmap(
                slot_fn, out_axes=(0, 0, 1, 1))(tables, lengths, nxt)
            return toks, nxts, rows_k, rows_v

        def rows_in_place(params, k_pool, v_pool, tables, lengths, nxt):
            with jax.named_scope("decode_pages"):
                toks, nxts, rows_k, rows_v = model.decode_rows_paged(
                    params, k_pool, v_pool, tables, lengths, nxt[:, 0], T)
            return toks, nxts[:, None], rows_k, rows_v

        rows_fn = rows_in_place if self.in_place else gathered_rows

        def decode_paged(params, k_pool, v_pool, tables, lengths, nxt):
            toks, nxts, rows_k, rows_v = rows_fn(
                params, k_pool, v_pool, tables, lengths, nxt)

            def body(b, pools):
                kp, vp = pools
                tbl = tables[b]
                start = jnp.minimum(lengths[b], S - T)
                kp = scatter_block_rows(kp, tbl, rows_k[:, b], start)
                vp = scatter_block_rows(vp, tbl, rows_v[:, b], start)
                return kp, vp

            with jax.named_scope("kv_scatter"):
                k_pool, v_pool = jax.lax.fori_loop(
                    0, tables.shape[0], body, (k_pool, v_pool))
            return k_pool, v_pool, toks, nxts

        self._decode_slots = engine.tracker.wrap(
            jax.jit(decode_paged), "decode_slots")

        def extend_paged(params, k_pool, v_pool, table, start, tokens):
            with jax.named_scope("kv_gather"):
                st = {"k": gather_block_table(k_pool, table),
                      "v": gather_block_table(v_pool, table),
                      "length": start}
            with jax.named_scope("extend"):
                st, logits = model.prefill_extend(params, st, tokens)
            C = tokens.shape[1]
            with jax.named_scope("kv_rows"):
                rk = jax.lax.dynamic_slice(
                    st["k"], (0, 0, start, 0, 0), (L, 1, C, Hk, hd))[:, 0]
                rv = jax.lax.dynamic_slice(
                    st["v"], (0, 0, start, 0, 0), (L, 1, C, Hk, hd))[:, 0]
            with jax.named_scope("kv_scatter"):
                k_pool = scatter_block_rows(k_pool, table, rk, start)
                v_pool = scatter_block_rows(v_pool, table, rv, start)
            return k_pool, v_pool, logits

        self._extend = engine.tracker.wrap(
            jax.jit(extend_paged), "prefill_extend")

    # -- page accounting ---------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        return self.pages - len(self._free) - len(self._cached)

    def _take_pages(self, n: int) -> Optional[List[int]]:
        """n fresh pages, reclaiming cached prefix pages if needed."""
        got: List[int] = []
        while len(got) < n:
            if self._free:
                got.append(self._free.pop())
            elif self._cached:
                page, _ = self._cached.popitem(last=False)   # oldest
                key = self._key_of.pop(page)
                self._page_of.pop(key, None)
                got.append(page)
            else:
                self._free.extend(got)                       # rollback
                return None
        self.pages_allocated += len(got)
        self.pages_peak = max(self.pages_peak, self.pages_in_use)
        return got

    def _drop_ref(self, page: int) -> None:
        self._ref[page] -= 1
        if self._ref[page] == 0:
            if page in self._key_of:
                self._cached[page] = None            # reusable, reclaimable
            else:
                self._free.append(page)

    def _register(self, page: int, key: bytes) -> None:
        if key not in self._page_of and page not in self._key_of:
            self._page_of[key] = page
            self._key_of[page] = key

    # -- prefill -----------------------------------------------------------
    def begin(self, slot: int, tix: "RequestTicket") -> bool:
        prompt = np.asarray(tix.request.prompt, np.int32)
        plen = len(prompt)
        chain: List[bytes] = []
        key = b"kv-root"
        for p in range(plen // self.pt):             # fully-covered pages
            key = _hash_page(key, prompt[p * self.pt:(p + 1) * self.pt])
            chain.append(key)
        # shareable prefix: page span must end before the last prompt token
        # so at least one token remains to prefill (tok0 needs logits)
        n_share_max = (plen - 1) // self.pt
        shared: List[int] = []
        for p in range(min(n_share_max, len(chain))):
            pg = self._page_of.get(chain[p])
            if pg is None:
                break
            # pin BEFORE allocating: a refcount-0 shared page sits in
            # self._cached, which _take_pages reclaims under pool pressure
            # — left unpinned, the same physical page could be handed back
            # as a fresh prefill target and the prefill would clobber the
            # shared prefix content
            if self._ref[pg] == 0:
                self._cached.pop(pg, None)
            self._ref[pg] += 1
            shared.append(pg)
        n_total = -(-plen // self.pt)                # pages covering prompt
        got = self._take_pages(n_total - len(shared))
        if got is None:
            for pg in shared:                        # unpin: roll back
                self._drop_ref(pg)
            return False
        for pg in got:
            self._ref[pg] += 1
        self.tables[slot, :n_total] = shared + got
        self.tables[slot, n_total:] = 0
        self.n_rows[slot] = n_total
        self.lengths[slot] = 0
        self.ready[slot] = False
        self._chain[slot] = chain
        start = len(shared) * self.pt
        chunked = bool(self.chunk) and (plen - start) > self.chunk
        self._pending[slot] = {"tix": tix, "prompt": prompt, "pos": start,
                               "chunked": chunked}
        if chunked:
            self.counters.chunked_prompts += 1
        if shared:
            self.prefix_hits += 1
            self.pages_reused += len(shared)
            self.prefix_hit_tokens += start
            self.engine.session.emit(
                "progress", "kv.prefix_hit", uid=tix.uid, slot=slot,
                pages=len(shared), tokens=start,
                payload_bytes=len(shared) * self.page_bytes)
        if got:
            self.engine.session.emit(
                "progress", "kv.alloc", uid=tix.uid, slot=slot,
                pages=len(got), payload_bytes=len(got) * self.page_bytes)
        return True

    def prefill_step(self, slot: int) -> Optional[int]:
        p = self._pending[slot]
        tix, prompt, pos = p["tix"], p["prompt"], p["pos"]
        eng = self.engine
        plen = len(prompt)
        remaining = plen - pos
        c = min(self.chunk, remaining) if p["chunked"] else remaining
        span_name = "serve.prefill_chunk" if p["chunked"] else "serve.prefill"
        table = jnp.asarray(self.tables[slot])
        pairs = self.counters.count_extend(pos, c, self.n_blk * self.pt)
        with eng.session.span(span_name, uid=tix.uid, start=pos, size=c,
                              prompt_len=plen, **pairs):
            self.k_pool, self.v_pool, logits = self._extend(
                eng.params, self.k_pool, self.v_pool, table,
                jnp.asarray(pos, jnp.int32), jnp.asarray(prompt[None,
                                                                pos:pos + c]))
        p["pos"] = pos + c
        self.counters.prefill_launches += 1
        if p["chunked"]:
            self.counters.prefill_chunk_launches += 1
        self.counters.prefill_tokens += c
        tix.n_prefill_launches += 1
        if p["pos"] < plen:
            return None
        # prompt fully in cache: register shareable pages, go decodable.
        # Pages overlapping [max_seq - T, max_seq) are excluded: a slot
        # finishing at the KV cap scatter-writes its clamped decode rows
        # there (scatter_block_rows start = min(length, S - T)), and a
        # registered page must stay immutable once other requests attach
        # to it — reachable when page_tokens < tokens_per_launch (the
        # tuner ladder offers page_tokens=4 against T=8).
        chain = self._chain[slot]
        n_reg = min(plen // self.pt,
                    (eng.max_seq - eng.T) // self.pt)
        for i in range(n_reg):
            self._register(int(self.tables[slot, i]), chain[i])
        with eng.session.span("serve.prefill.readback"):
            tok0 = int(jnp.argmax(logits[0, -1, :]))
        self.lengths[slot] = plen
        self.ready[slot] = True
        self._nxt = self._nxt.at[slot, 0, 0].set(tok0)
        del self._pending[slot]
        return tok0

    # -- decode ------------------------------------------------------------
    def reserve_decode(self, slots: List[int]) -> List[int]:
        """Grow block tables to cover the next T decode writes.

        Returns slots the pool cannot serve (after reclaiming every cached
        page) — the engine evicts those with ``reason="kv_pages"`` and
        calls again, so freed pages immediately serve the survivors.  At
        most ONE victim is returned per call: several slots crossing a
        page boundary in the same iteration must not all be evicted when
        freeing a single one would let the rest grow.
        """
        victims: List[int] = []
        for slot in slots:
            ln = int(self.lengths[slot])
            last = min(ln + self.engine.T, self.engine.max_seq) - 1
            need = last // self.pt + 1 - int(self.n_rows[slot])
            if need <= 0:
                continue
            got = self._take_pages(need)
            if got is None:
                victims.append(slot)
                return victims
            r0 = int(self.n_rows[slot])
            self.tables[slot, r0:r0 + need] = got
            self.n_rows[slot] = r0 + need
            for pg in got:
                self._ref[pg] += 1
            self.engine.session.emit(
                "progress", "kv.alloc", uid=self._uid(slot), slot=slot,
                pages=need, payload_bytes=need * self.page_bytes)
        return victims

    def _uid(self, slot: int) -> int:
        tix = self.engine._slot_tix[slot]
        return tix.uid if tix is not None else -1

    def decode(self) -> np.ndarray:
        # still-prefilling slots decode as empty scratch slots: their block
        # tables and lengths are masked so the launch never touches their
        # half-written pages.  The dispatch between the two spans stays
        # stamped with the caller's span (serve.decode_iter).
        sess = self.engine.session
        with sess.span("serve.decode.upload"):
            tables = jnp.asarray(np.where(self.ready[:, None], self.tables, 0))
            lengths = jnp.asarray(
                np.where(self.ready, self.lengths, 0).astype(np.int32))
        self.k_pool, self.v_pool, toks, self._nxt = self._decode_slots(
            self.engine.params, self.k_pool, self.v_pool, tables, lengths,
            self._nxt)
        self.counters.decode_launches += 1
        # pages the attention reads: the kernel the live ones below each
        # slot's window start, the gather every table row
        active = self.lengths[self.ready]
        table = len(active) * self.n_blk
        read = table
        if self.in_place:
            start = np.minimum(active, self.engine.max_seq - self.engine.T)
            read = int(np.sum(-(-start // self.pt)))
        self.counters.decode_pages_table += table
        self.counters.decode_pages_read += read
        with sess.span("serve.decode.readback"):
            blocks = np.asarray(toks)                # [B, T] host sync
        self.lengths[self.ready] += self.engine.T
        return blocks

    def release(self, slot: int) -> None:
        n = int(self.n_rows[slot])
        freed = 0
        for i in range(n):
            self._drop_ref(int(self.tables[slot, i]))
            freed += 1
        if freed:
            self.engine.session.emit(
                "progress", "kv.free", uid=self._uid(slot), slot=slot,
                pages=freed, payload_bytes=freed * self.page_bytes)
        self.tables[slot, :] = 0
        self.n_rows[slot] = 0
        self.lengths[slot] = 0
        self.ready[slot] = False
        self._chain.pop(slot, None)
        self._pending.pop(slot, None)

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "backend": self.name,
            "prefill_chunk": self.chunk,
            "page_tokens": self.pt,
            "pages_total": self.pages,
            "pages_in_use": self.pages_in_use,
            "pages_peak": self.pages_peak,
            "pages_allocated": self.pages_allocated,
            "pages_reused": self.pages_reused,
            "pages_cached": len(self._cached),
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
        }
        out.update(self.counters.to_dict())
        return out


def make_kv(engine: "Server", kind: str = "dense",
            page_tokens: int = 16, pages: Optional[int] = None,
            prefill_chunk: int = 0) -> KVCacheManager:
    if kind == "dense":
        return DenseKV(engine, prefill_chunk=prefill_chunk)
    if kind == "paged":
        return PagedKV(engine, page_tokens=page_tokens, pages=pages,
                       prefill_chunk=prefill_chunk)
    raise ValueError(f"unknown kv backend {kind!r}; "
                     f"expected one of {KV_BACKENDS}")
