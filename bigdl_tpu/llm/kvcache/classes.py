"""Page classes: the kinds of cache a family's layers keep, side by side
(ISSUE 31; docs/KVCACHE.md "What a family gives the engine").

A family's layers need not all cache alike. Some keep every token (a
full-attention layer: its pages grow with the context), some the last
``W`` positions (a sliding-window layer), and each kind may have its
own number of KV heads and its own K and V widths. A family says so
with ``page_classes(cfg)`` -> ``[PageClass, ...]``; one that does not is
given ONE class from its configuration (:func:`page_classes_of`), so
Llama and every latent family go through the same declaration.

The engine builds one pool pair and one block table a class. The class
that keeps every token is the one the :class:`KVCacheManager` has always
managed (position-indexed table, admission by worst-case length, one
page granted every ``page`` steps). A class that keeps a window is a
**ring** (:class:`RingLedger`): ``ring_pages(W, page)`` table columns a
request, the token at position ``p`` in column ``(p // page) % ring``,
granted as the first ``ring * page`` positions arrive and never again:
the pages a request holds in that class are bounded by the ring
whatever its length, and all of them come back on release. (A ring,
not pages freed as they leave the window: the table stays ``ring``
columns wide instead of ``max_seq_len / page``, nothing is granted or
freed per step once it is full, and the kernels rebuild positions from
the length, :func:`kernels.hybrid_attention.ring_positions`.)

A third kind keeps **no token at all** (ISSUE 33; docs/KVCACHE.md "State
classes"): a :class:`StateClass` is state that an engine *slot* holds,
the same size at position 10 and at position 30,000, rewritten whole at
every token by the family's own kernels. It has no pages and no block
table: the engine builds ``1 + max_batch`` state rows a layer (row 0
the trash row), seats a request in the row of its slot
(:class:`StateLedger`), grants nothing as it decodes, and the prefill
program that first writes a seated row takes what it holds as zero, in
every array of the class. **A class names its arrays** (ISSUE 37): a
retention layer a matrix a head and its normaliser, a state-space layer
a matrix a head and the last inputs of its convolution. A family may
declare state classes **beside page classes** (a request then holds
pages and a slot's row at once, admitted and released together), or, as
a retention model does, nothing else.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from bigdl_tpu.llm.kvcache.pool import PagePool


@dataclasses.dataclass(frozen=True)
class PageClass:
    """One kind of cache. ``layers`` cache in it, each ``kv_heads`` rows
    of ``k_width`` (and ``v_width``: None for one pool of rows and no V
    pool, a latent cache) numbers a token, at the widths the pools are
    held at; ``keeps`` None keeps every token, ``W`` the last ``W``
    positions."""
    name: str
    layers: int
    kv_heads: int
    k_width: int
    v_width: Optional[int]
    keeps: Optional[int] = None

    def pools(self, num_pages: int, page: int, dtype) -> Tuple:
        import jax.numpy as jnp
        shape = (self.layers, num_pages, self.kv_heads, page)
        return (jnp.zeros(shape + (self.k_width,), dtype),
                None if self.v_width is None
                else jnp.zeros(shape + (self.v_width,), dtype))


@dataclasses.dataclass(frozen=True)
class StateClass:
    """State a slot holds, whatever its request's length: ``layers``
    keep, each, the arrays the class **names**. ``holds`` is one or two
    ``(name, shape, dtype)``, the shape a slot's of one layer (the
    engine hands a class's arrays to the programs where a page class's
    K and V pools go, so there are at most two): a state-space layer
    names a matrix a head and the convolution's last inputs.

    A retention layer's two arrays have a shorthand, the class's first
    form: ``heads``, ``rows``, ``width`` and ``dtype`` name a matrix of
    ``width`` rows of ``rows`` numbers a head (held transposed, the long
    axis last) and a vector of ``rows``; given them, ``holds`` is made
    from them (so ``dataclasses.replace(cls, dtype=...)`` is another
    precision of the same class)."""
    name: str
    layers: int
    heads: int = 0
    rows: int = 0
    width: int = 0
    dtype: str = "float32"
    holds: Tuple[Tuple[str, Tuple[int, ...], str], ...] = ()

    def __post_init__(self):
        holds = self.holds
        if self.heads:
            holds = (("state", (self.heads, self.width, self.rows),
                      self.dtype),
                     ("z", (self.heads, self.rows), self.dtype))
        holds = tuple((str(n), tuple(int(d) for d in shape), str(dt))
                      for n, shape, dt in holds)
        if not 1 <= len(holds) <= 2:
            raise ValueError(
                f"state class {self.name!r} names {len(holds)} arrays; "
                "a class's arrays go where a K and a V pool go: one or "
                "two")
        object.__setattr__(self, "holds", holds)

    def arrays(self, max_batch: int) -> Tuple:
        """The class's arrays for ``max_batch`` slots and the trash
        row, each ``(layers, 1 + max_batch) + shape``, zeros; None in
        the second place where the class names one."""
        import jax.numpy as jnp
        made = tuple(jnp.zeros((self.layers, 1 + max_batch) + shape, dt)
                     for _, shape, dt in self.holds)
        return made + (None,) * (2 - len(made))

    @property
    def slot_bytes(self) -> int:
        """Bytes one seated slot holds."""
        import jax.numpy as jnp      # knows bfloat16, as numpy does not
        return self.layers * sum(
            int(np.prod(shape)) * jnp.dtype(dt).itemsize
            for _, shape, dt in self.holds)


def every_token_class(classes) -> Optional[PageClass]:
    """The class that keeps every token, if the family has one."""
    first = classes[0]
    return first if isinstance(first, PageClass) and first.keeps is None \
        else None


def page_classes_of(fam_mod, cfg) -> list:
    """What the family declares, or the one class of a family that does
    not: a K and a V pool of per-head rows for every layer. At most one
    class keeps every token, and it comes first; then the classes that
    keep a window; then the state classes."""
    declare = getattr(fam_mod, "page_classes", None)
    if declare is None:
        return [PageClass("kv", cfg.num_hidden_layers,
                          cfg.num_key_value_heads, cfg.head_dim,
                          cfg.head_dim)]
    classes = list(declare(cfg))
    kinds = [2 if isinstance(c, StateClass) else int(c.keeps is not None)
             for c in classes]
    if not classes or kinds.count(0) > 1 or kinds != sorted(kinds):
        raise ValueError(
            f"{fam_mod.__name__}.page_classes: at most one class keeps "
            "every token and it comes first, then the window classes, "
            f"then the state classes; got {classes}")
    return classes


class RingLedger:
    """Host bookkeeping of one window class: its own :class:`PagePool`
    (page 0 the trash page, as everywhere), the ring table of every
    engine slot and the pages each slot holds. The pool is sized for
    every slot's whole ring, so admission never waits on it; the budget
    is charged all the same, so that the ledger balances like the
    full class's."""

    def __init__(self, cls: PageClass, page: int, max_batch: int):
        from bigdl_tpu.llm.kernels.hybrid_attention import ring_pages
        self.cls = cls
        self.page = page
        self.ring = ring_pages(cls.keeps, page)
        self.num_pages = 1 + max_batch * self.ring
        self.pool = PagePool(self.num_pages, page)
        self.bt = np.zeros((max_batch, self.ring), np.int32)
        self.owned: List[List[int]] = [[] for _ in range(max_batch)]
        self.charge = [0] * max_batch

    def pages_for(self, tokens: int) -> int:
        """Pages a request of ``tokens`` positions ever holds here."""
        return min(self.ring, -(-tokens // self.page))

    def admit(self, slot: int, tokens: int) -> bool:
        n = self.pages_for(tokens)
        if n > self.pool.budget_avail:
            return False
        self.pool.charge(n)
        self.charge[slot] = n
        return True

    def grant(self, slot: int, tokens: int) -> List[Tuple[int, int]]:
        """Pages for positions ``0 .. tokens - 1`` that the slot does
        not hold yet: ``[(column, page id), ...]`` newly in its ring
        table (none once the ring is full)."""
        have = len(self.owned[slot])
        new = []
        for col in range(have, self.pages_for(tokens)):
            pid = self.pool.take_free()
            self.bt[slot, col] = pid
            self.owned[slot].append(pid)
            new.append((col, pid))
        return new

    def release(self, slot: int) -> int:
        """Everything the slot holds goes back; returns how many pages."""
        pages, self.owned[slot] = self.owned[slot], []
        for pid in pages:
            self.pool.decref(pid)
        self.pool.release(self.charge[slot])
        self.charge[slot] = 0
        self.bt[slot, :] = 0
        return len(pages)

    def pages_in_use(self) -> int:
        return sum(len(p) for p in self.owned)

    def scatter_targets(self, slot: int, positions: np.ndarray,
                        upto: int) -> np.ndarray:
        """The page each of ``positions`` is written to (0, the trash
        page, from position ``upto`` on)."""
        cols = (positions // self.page) % self.ring
        return np.where(positions < upto, self.bt[slot, cols],
                        0).astype(np.int32)


class StateLedger:
    """Host bookkeeping of one state class: which slot is seated in its
    state row (slot ``i`` holds row ``1 + i``; row 0 is the trash row),
    how often a row was seated and so taken as zero, and the bytes
    held. There is nothing to run out of: a free slot has its row."""

    def __init__(self, cls: StateClass, max_batch: int):
        self.cls = cls
        self.seated = [False] * max_batch
        # how often each slot was seated: from the second time on its
        # row held another request's state when it was taken as zero
        self.seatings = [0] * max_batch
        # the table the programs take, and it never changes: a slot's
        # state row (a slot that sits a step out is sent to row 0 by
        # the sampled step's mask, as a page table's rows are)
        self.rows = 1 + np.arange(max_batch, dtype=np.int32)[:, None]

    def seat(self, slot: int) -> int:
        """Seat a request: its state row, which the prefill program
        that writes it first takes as zero."""
        if self.seated[slot]:
            raise ValueError(f"slot {slot} is seated already")
        self.seated[slot] = True
        self.seatings[slot] += 1
        return int(self.rows[slot, 0])

    def release(self, slot: int) -> int:
        """The slot's row goes back (what it holds stays until the next
        occupant's prefill takes it as zero); returns the rows freed."""
        was = self.seated[slot]
        self.seated[slot] = False
        return int(was)

    def slots_in_use(self) -> int:
        return sum(self.seated)

    def bytes_held(self) -> int:
        return self.slots_in_use() * self.cls.slot_bytes
