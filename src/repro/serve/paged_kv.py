"""Paged KV cache: fixed-size pages, one block table for every plane.

The contiguous per-sequence cache of ``models.init_cache`` wastes
``max_ctx`` slots per slot-holder; under continuous batching the live
lengths are ragged and churn every few steps.  This module stores the
cache as ``(L, num_pages, page_size, ...)`` pools ("planes" — one per
cached tensor) plus per-sequence page lists, so memory scales with the sum
of live lengths rounded up to a page.

Planes by attention kind: grouped-query attention caches ``k`` and ``v``,
each ``(L, num_pages, page_size, KV, hd)``; latent attention (MLA) caches
the ``c_kv`` latent ``(L, num_pages, page_size, kv_lora_rank)`` and the
rotated ``k_rope`` ``(L, num_pages, page_size, rope_dim)``.  Both kinds
share the block table, the free list and the page accounting.

Float-float pages: in ``kv_mode="ff_bf16"`` each of k/v splits into an
FF-style hi/lo limb pair (``hi = bf16(x)``, ``lo = bf16(x - hi)`` —
double-bf16, the storage analogue of the paper's double-f32 operators).
The limb planes are NOT independently paged: every plane indexes through
the SAME block table, so allocation, eviction and serialization always
move the hi and lo limbs of a value together — an FF number never has its
limbs split across inconsistent pages.

All host-side state (block table, free list, lengths) is numpy, and
:meth:`to_state` / :meth:`from_state` round-trip the whole cache through
a plain dict of numpy arrays (serialization-safe: no jax types, no python
objects beyond the dict).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

Array = jnp.ndarray

#: plane-name suffixes per kv_mode (all planes share the block table)
_MODE_SUFFIXES = {"bf16": ("",), "f32": ("",), "ff_bf16": ("_hi", "_lo")}
_MODE_DTYPE = {"bf16": jnp.bfloat16, "f32": jnp.float32,
               "ff_bf16": jnp.bfloat16}
#: the cached tensors of each attention kind
KIND_BASES = {"gqa": ("k", "v"), "mla": ("c_kv", "k_rope")}


def _plane_names(kind: str, kv_mode: str):
    return tuple(b + sfx for b in KIND_BASES[kind]
                 for sfx in _MODE_SUFFIXES[kv_mode])


def ff_split(x: Array, dtype=jnp.bfloat16):
    """Split an f32 array into (hi, lo) storage limbs: ``hi = round(x)``,
    ``lo = round(x - hi)``.  Exact Fast2Sum-style residual at the storage
    precision (the subtraction is exact in f32 because hi has f32-width
    significand content truncated to ``dtype``)."""
    xf = jnp.asarray(x, jnp.float32)
    hi = xf.astype(dtype)
    lo = (xf - hi.astype(jnp.float32)).astype(dtype)
    return hi, lo


def ff_merge(hi: Array, lo: Array) -> Array:
    """Rebuild the f32 value from storage limbs (exact sum in f32)."""
    return hi.astype(jnp.float32) + lo.astype(jnp.float32)


class PagedKVCache:
    """Fixed-pool paged KV store for ``max_seqs`` concurrent sequences.

    Planes are jnp arrays of shape ``(L, num_pages, page_size, *tail)``:
    ``tail`` is ``(KV, hd)`` for the GQA planes and ``(r,)`` /
    ``(rope_dim,)`` for MLA's (``latent=(r, rope_dim)``); the block table
    is numpy ``(max_seqs, max_pages)`` int32 with ``-1`` marking
    unallocated entries.  Page 0..num_pages-1 are real; the engine uses
    index ``num_pages`` as the out-of-bounds "drop" target for inactive
    rows (``.at[...].set(mode="drop")``).
    """

    def __init__(self, num_layers: int, num_kv_heads: int, head_dim: int, *,
                 num_pages: int, page_size: int = 16, max_seqs: int = 8,
                 max_ctx: int = 512, kv_mode: str = "bf16",
                 latent: Optional[Tuple[int, int]] = None):
        if kv_mode not in _MODE_SUFFIXES:
            raise ValueError(f"unknown kv_mode {kv_mode!r}; "
                             f"choose from {tuple(_MODE_SUFFIXES)}")
        self.num_layers = num_layers
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.latent = None if latent is None else tuple(int(n)
                                                        for n in latent)
        self.kind = "gqa" if latent is None else "mla"
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_seqs = max_seqs
        self.max_pages = -(-max_ctx // page_size)   # pages per sequence row
        self.kv_mode = kv_mode
        dt = _MODE_DTYPE[kv_mode]
        self.planes: Dict[str, Array] = {
            name: jnp.zeros(self._shape(name), dt)
            for name in _plane_names(self.kind, kv_mode)}
        self.block_table = np.full((max_seqs, self.max_pages), -1, np.int32)
        self.seq_lens = np.zeros((max_seqs,), np.int32)
        self.free_pages: List[int] = list(range(num_pages - 1, -1, -1))

    @classmethod
    def for_config(cls, cfg, **kw) -> "PagedKVCache":
        """The planes a model config's attention caches: MLA's latent
        pair when ``cfg.use_mla``, else GQA's K/V."""
        latent = ((cfg.kv_lora_rank, cfg.qk_rope_head_dim) if cfg.use_mla
                  else None)
        return cls(cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim,
                   latent=latent, **kw)

    @property
    def bases(self) -> Tuple[str, ...]:
        """The cached tensors (``k``/``v`` or ``c_kv``/``k_rope``)."""
        return KIND_BASES[self.kind]

    def tail(self, base: str) -> Tuple[int, ...]:
        """A cached tensor's per-position shape."""
        if self.kind == "gqa":
            return (self.num_kv_heads, self.head_dim)
        return (self.latent[self.bases.index(base)],)

    def _shape(self, name: str) -> Tuple[int, ...]:
        base = next(b for b in self.bases if name.startswith(b))
        return (self.num_layers, self.num_pages, self.page_size) \
            + self.tail(base)

    # -- allocation --------------------------------------------------------

    def pages_for(self, length: int) -> int:
        return -(-length // self.page_size)

    def can_alloc(self, length: int) -> bool:
        return len(self.free_pages) >= self.pages_for(length)

    def alloc(self, slot: int, length: int) -> List[int]:
        """Allocate pages for a sequence of ``length`` tokens in ``slot``.
        Returns the page ids (also recorded in the block table)."""
        need = self.pages_for(length)
        if need > self.max_pages:
            raise ValueError(f"length {length} exceeds max_ctx "
                             f"({self.max_pages * self.page_size})")
        if need > len(self.free_pages):
            raise RuntimeError("paged KV pool exhausted")
        if self.seq_lens[slot] or (self.block_table[slot] >= 0).any():
            raise RuntimeError(f"slot {slot} already holds a sequence")
        ids = [self.free_pages.pop() for _ in range(need)]
        self.block_table[slot, :need] = ids
        self.seq_lens[slot] = length
        return ids

    def grow(self, slot: int, new_length: int) -> Optional[int]:
        """Extend ``slot`` to ``new_length`` tokens, allocating at most one
        new page (decode adds one token per step).  Returns the new page id
        or None if the current last page still has room."""
        have = self.pages_for(int(self.seq_lens[slot]))
        need = self.pages_for(new_length)
        if need <= have:
            self.seq_lens[slot] = new_length
            return None
        if need - have != 1:
            raise ValueError("grow() extends by at most one page")
        if not self.free_pages:
            # raise BEFORE touching seq_lens: a failed grow must leave the
            # bookkeeping exactly as it was (check_integrity-clean), so
            # the engine can preempt a neighbor and retry
            raise RuntimeError("paged KV pool exhausted")
        pid = self.free_pages.pop()
        self.block_table[slot, have] = pid
        self.seq_lens[slot] = new_length
        return pid

    def check_integrity(self):
        """Audit the host-side paging metadata (block table + free list).

        Returns ``(problems, bad_slots)``: human-readable descriptions and
        the set of slots whose page lists can no longer be trusted (an
        out-of-range page id, a page shared between two slots or with the
        free list, or a hole below the live length).  Pure numpy scan of
        ``max_seqs * max_pages`` entries — cheap enough to run per
        scheduler step under ``ff.guard``.  The caller decides what to do
        with the verdict (the serve engine quarantines the slots, zeroes
        their rows and calls :meth:`rebuild_free_list`)."""
        problems: List[str] = []
        bad = set()
        free = [int(p) for p in self.free_pages]
        free_set = set(free)
        if len(free_set) != len(free):
            problems.append("free list contains duplicate page ids")
        if any(not 0 <= p < self.num_pages for p in free_set):
            problems.append("free list contains out-of-range page ids")
        owner: Dict[int, int] = {}
        for slot in range(self.max_seqs):
            row = self.block_table[slot]
            for pid in row:
                pid = int(pid)
                if pid == -1:
                    continue
                if not 0 <= pid < self.num_pages:
                    problems.append(f"slot {slot}: page id {pid} out of "
                                    f"range [0, {self.num_pages})")
                    bad.add(slot)
                    continue
                if pid in free_set:
                    problems.append(f"slot {slot}: page {pid} is also on "
                                    f"the free list")
                    bad.add(slot)
                if pid in owner:
                    problems.append(f"page {pid} referenced by slots "
                                    f"{owner[pid]} and {slot}")
                    bad.add(slot)
                    bad.add(owner[pid])
                else:
                    owner[pid] = slot
            live = self.pages_for(int(self.seq_lens[slot]))
            if live and (row[:live] < 0).any():
                problems.append(f"slot {slot}: missing page below live "
                                f"length {int(self.seq_lens[slot])}")
                bad.add(slot)
        for name in _plane_names(self.kind, self.kv_mode):
            got = tuple(self.planes[name].shape) \
                if name in self.planes else None
            if got != self._shape(name):
                problems.append(f"plane {name}: shape {got} != "
                                f"{self._shape(name)}")
                bad.update(range(self.max_seqs))
        return problems, bad

    def rebuild_free_list(self) -> None:
        """Recompute the free list as every in-range page not referenced by
        the block table (recovery path after :meth:`check_integrity` found
        corruption and the caller cleared the untrusted rows)."""
        used = {int(p) for p in self.block_table.ravel()
                if 0 <= int(p) < self.num_pages}
        self.free_pages = [p for p in range(self.num_pages - 1, -1, -1)
                           if p not in used]

    def drop_slot(self, slot: int) -> None:
        """Clear a slot's row WITHOUT returning its pages to the free list
        (quarantine path: the row's page ids are untrusted — follow with
        :meth:`rebuild_free_list` once every bad row is cleared)."""
        self.block_table[slot] = -1
        self.seq_lens[slot] = 0

    def free_slot(self, slot: int) -> None:
        """Evict a sequence: return its pages to the free list.  Page
        contents are left as-is (stale but finite — masked reads contribute
        exact zeros), so eviction is O(pages) host work with no device op."""
        for pid in self.block_table[slot]:
            if pid >= 0:
                self.free_pages.append(int(pid))
        self.block_table[slot] = -1
        self.seq_lens[slot] = 0

    # -- data movement -----------------------------------------------------

    def write_prefill(self, slot: int, tensors: Dict[str, Array]) -> None:
        """Write per-layer contiguous cache tensors (GQA ``{"k": (L, S, KV,
        hd), "v": ...}``, MLA ``{"c_kv": (L, S, r), "k_rope": (L, S,
        rope_dim)}``, in compute f32/bf16) into this slot's pages.  In FF
        mode the values are limb-split here; both limbs land in the same
        pages."""
        S = int(tensors[self.bases[0]].shape[1])
        if S != int(self.seq_lens[slot]):
            raise ValueError("prefill length != allocated length")
        npg = self.pages_for(S)
        ids = self.block_table[slot, :npg]
        pad = npg * self.page_size - S
        for base in self.bases:
            x = jnp.asarray(tensors[base])
            if pad:
                x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            paged = x.reshape((x.shape[0], npg, self.page_size)
                              + self.tail(base))
            if self.kv_mode == "ff_bf16":
                hi, lo = ff_split(paged)
                self.planes[f"{base}_hi"] = \
                    self.planes[f"{base}_hi"].at[:, ids].set(hi)
                self.planes[f"{base}_lo"] = \
                    self.planes[f"{base}_lo"].at[:, ids].set(lo)
            else:
                dt = self.planes[base].dtype
                self.planes[base] = \
                    self.planes[base].at[:, ids].set(paged.astype(dt))

    def gather(self, slot: int) -> Dict[str, Array]:
        """Contiguous read-back of a slot (``{base: (L, S, *tail)}``, f32
        in FF mode, storage dtype otherwise).  Host/debug path — the engine
        gathers on-device inside its jitted step instead."""
        S = int(self.seq_lens[slot])
        npg = self.pages_for(S)
        ids = self.block_table[slot, :npg]
        out = {}
        for base in self.bases:
            if self.kv_mode == "ff_bf16":
                hi = self.planes[f"{base}_hi"][:, ids]
                lo = self.planes[f"{base}_lo"][:, ids]
                paged = ff_merge(hi, lo)
            else:
                paged = self.planes[base][:, ids]
            out[base] = paged.reshape(
                (self.num_layers, npg * self.page_size)
                + self.tail(base))[:, :S]
        return out

    # -- serialization -----------------------------------------------------

    def to_state(self) -> Dict[str, np.ndarray]:
        """Whole cache as a flat dict of numpy arrays (plus scalars of the
        geometry).  bf16 planes ship as uint16 bit patterns so the dict
        round-trips through any numpy-only container (npz, plasma, ...).
        MLA's adds ``latent`` (r, rope_dim); GQA's holds what it always
        held."""
        state: Dict[str, np.ndarray] = {
            "block_table": self.block_table.copy(),
            "seq_lens": self.seq_lens.copy(),
            "free_pages": np.asarray(self.free_pages, np.int32),
            "geometry": np.asarray(
                [self.num_layers, self.num_kv_heads, self.head_dim,
                 self.num_pages, self.page_size, self.max_seqs,
                 self.max_pages * self.page_size], np.int64),
            "kv_mode": np.frombuffer(
                self.kv_mode.encode().ljust(8, b"\0"), np.uint8).copy(),
        }
        if self.latent is not None:
            state["latent"] = np.asarray(self.latent, np.int64)
        for name, plane in self.planes.items():
            arr = np.asarray(plane)
            if arr.dtype == jnp.bfloat16:
                arr = arr.view(np.uint16)
            state[f"plane_{name}"] = arr
        return state

    @classmethod
    def from_state(cls, state: Dict[str, np.ndarray]) -> "PagedKVCache":
        """Rebuild a cache from :meth:`to_state` output.  The container
        (``repro.checkpoint``) guarantees bit integrity via CRC32; this
        validates STRUCTURE — missing keys, a malformed geometry vector,
        or plane shapes disagreeing with it raise ``ValueError`` instead
        of constructing a cache that decodes garbage."""
        for key in ("geometry", "kv_mode", "block_table", "seq_lens",
                    "free_pages"):
            if key not in state:
                raise ValueError(f"KV state missing required key {key!r}")
        geom = np.asarray(state["geometry"]).ravel()
        if geom.shape[0] != 7:
            raise ValueError(f"KV state geometry has {geom.shape[0]} "
                             f"entries; expected 7")
        L, KV, hd, NP, ps, ms, mc = (int(v) for v in geom)
        mode = bytes(state["kv_mode"]).rstrip(b"\0").decode()
        if mode not in _MODE_SUFFIXES:
            raise ValueError(f"KV state names unknown kv_mode {mode!r}")
        latent = None
        if "latent" in state:
            latent = tuple(int(v) for v in np.asarray(state["latent"]).ravel())
            if len(latent) != 2:
                raise ValueError(f"KV state latent has {len(latent)} "
                                 f"entries; expected 2")
        self = cls(L, KV, hd, num_pages=NP, page_size=ps, max_seqs=ms,
                   max_ctx=mc, kv_mode=mode, latent=latent)
        names = _plane_names(self.kind, mode)
        for name in names:
            key = f"plane_{name}"
            if key not in state:
                raise ValueError(f"KV state missing plane {key!r} for "
                                 f"kv_mode {mode!r}")
            got = tuple(np.asarray(state[key]).shape)
            if got != self._shape(name):
                raise ValueError(f"KV state plane {key!r} shape {got} != "
                                 f"geometry {self._shape(name)}")
        self.block_table = np.asarray(state["block_table"], np.int32).copy()
        self.seq_lens = np.asarray(state["seq_lens"], np.int32).copy()
        self.free_pages = [int(p) for p in state["free_pages"]]
        dt = _MODE_DTYPE[mode]
        for name in names:
            arr = state[f"plane_{name}"]
            if dt == jnp.bfloat16:
                arr = arr.view(np.uint16)
                self.planes[name] = jnp.asarray(arr).view(jnp.bfloat16)
            else:
                self.planes[name] = jnp.asarray(arr, dt)
        return self
