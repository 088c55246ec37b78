"""What a model declares to the slot engine (``inference/serving/``).

A served module has ``decode()``, ``init_paged_cache()`` and
``slot_contract()``, which returns the one :class:`SlotContract` below.
``ServingEngine.__init__`` reads it ONCE (:func:`read`), holds it against the
cache the model builds (:func:`check`) and hands the value to the cache
manager and the program builders.  Nothing under ``serving/`` asks a module
or its config for anything else, and an absent name never means "behave as
another model": the defaults are written here, once.  The fields, with who
reads and who sets each: ``docs/serving.md`` "What a model declares".
"""

import dataclasses
from typing import Callable, Optional, Tuple

import jax


def _no_ring(page_size):
    return 0


def _no_fault(chunk):
    return None


def _quiet(default):
    return dataclasses.field(default=default, repr=False)


@dataclasses.dataclass(frozen=True)
class SlotContract:
    vocab_size: int
    max_seq_len: int
    dtype: str
    num_layers: int
    attention_bias: bool = False       # ALiBi: other registry kernel modes
    # ---- the cache ---- #
    kv_pages: bool = True              # the pools hold ``k`` / ``v`` pages
    # layers whose rows lie in the LANE pages — a dispatch's ``kv_pages``
    # are a layer's x these; None: all ``num_layers`` (a model whose other
    # layers keep a ring says how many do not: ``models/trinity.py``)
    lane_layers: Optional[int] = None
    lane_stride: int = 1               # positions a lane row stands for
    # ``ring_pages(page_size)``: pages of a ring the slot owns for good in a
    # pool of its own, behind the lane pages in its table row
    ring_pages: Callable[[int], int] = _quiet(_no_ring)
    row_kinds: Tuple[str, str] = ("lane rows", "ring rows")   # describe()'s
    # cache keys whose pools hold the rings: named, the cache manager counts
    # the rings' bytes beside the pages' (``ring_bytes_held``)
    ring_kinds: Tuple[str, ...] = ()
    # cache keys indexed by the slot's STATE ROW, its table row's last entry
    state_kinds: Tuple[str, ...] = ()
    # ---- the chunk program ---- #
    chunk_cap: int = 512               # the paged chunk kernel's bound
    # ``chunk_fault(chunk)``: why it cannot be the prefill chunk, or None
    chunk_fault: Callable[[int], Optional[str]] = _quiet(_no_fault)
    # a chunk geometry of the model's own (windows, latent lanes): ONE chunk
    # a dispatch, the scalar-``start`` program — said, never inferred
    own_chunk_path: bool = False
    # ---- experts ---- #
    routes_experts: bool = False       # dropless: mask dead rows, return load
    holds_share: bool = False          # the load's column of absent experts
    zero_experts: bool = False         # ... and of zero-compute choices
    expert_layers: int = 0             # the load vector is these ...
    experts: int = 0                   # ... x the experts held a layer
    # ---- self-drafting (``serving.spec_draft_model: "mtp"``) ---- #
    # layers the model's ``draft`` method runs: pool layers after the
    # model's, the load vector's last rows.  0: no such method
    draft_layers: int = 0
    # ---- attention work, as dispatch-span args: ``{name: count}`` ---- #
    # ``chunk_work(start, end, page_size, ring_pages, layers)``: a prefill
    # chunk over real positions ``start .. end - 1``;
    # ``block_work(live, ring_pages, layers)``: a decode dispatch, ``live``
    # its ``(context, steps)`` a live slot.  ``layers``: those the dispatch
    # ran (the model's, and under self-drafting the module's)
    chunk_work: Optional[Callable[..., dict]] = _quiet(None)
    block_work: Optional[Callable[..., dict]] = _quiet(None)
    work_counters: Tuple[str, ...] = ()    # names summed into ``srv.stats``
    work_levels: Tuple[str, ...] = ()      # names that are span args only

    @property
    def paged_layers(self):
        return self.num_layers if self.lane_layers is None \
            else self.lane_layers

    @property
    def drafts_itself(self):
        return self.draft_layers > 0

    @property
    def load_columns(self):
        """What an expert layer sows beside ``expert_tokens``, in the
        order of the load vector's columns after the held experts'."""
        return ("elsewhere",) * self.holds_share \
            + ("zero",) * self.zero_experts


def read(module):
    """``module.slot_contract()``, or a ``TypeError`` naming the class and
    what is missing."""
    name = type(module).__name__
    try:
        declare = module.slot_contract
    except AttributeError:
        raise TypeError(
            f"{name} has no slot_contract(): a model served through the "
            f"slot engine declares decode(), init_paged_cache() and "
            f"slot_contract() (models/contract.py)") from None
    try:
        contract = declare()
    except TypeError as e:      # a field SlotContract has not got, or lacks
        raise TypeError(f"{name}.slot_contract(): {e}") from None
    if not isinstance(contract, SlotContract):
        raise TypeError(f"{name}.slot_contract() returned "
                        f"{type(contract).__name__}, not a SlotContract")
    return contract


def check(contract, module, page_size, chunk, layers):
    """Hold ``contract`` against what ``module`` builds at the server's page
    and chunk: each fault a ``ValueError`` naming the class and the field.
    Shapes only, nothing is allocated."""
    said = f"{type(module).__name__}.slot_contract()"
    ring = contract.ring_pages(page_size)
    sizes = {"window_pages": 1 + ring} if ring else {}
    if contract.state_kinds:
        sizes["state_rows"] = 2
    try:
        pools = jax.eval_shape(
            lambda: module.init_paged_cache(2, page_size, **sizes))
    except TypeError as e:
        raise ValueError(
            f"{type(module).__name__}.init_paged_cache() does not take "
            f"{sorted(sizes)}, the sizes of what its slot_contract() "
            f"declares (ring_pages, state_kinds): {e}") from None
    for field in ("state_kinds", "ring_kinds"):
        missing = [k for k in getattr(contract, field) if k not in pools]
        if missing:
            raise ValueError(
                f"{said}: {field} names {missing}, no key of the cache "
                f"init_paged_cache() returns ({sorted(pools)})")
    for kind in contract.state_kinds:
        # whatever its dtype and its row's shape, a kind's pool is one row a
        # state row: ``paging.SlotPages`` counts a row's bytes kind by kind
        if pools[kind].ndim < 2 \
                or pools[kind].shape[1] != sizes["state_rows"]:
            raise ValueError(
                f"{said}: state kind {kind!r} is a pool of shape "
                f"{pools[kind].shape} at state_rows="
                f"{sizes['state_rows']}: its second axis is the state row")
    if contract.kv_pages != ("k" in pools):
        raise ValueError(f"{said}: kv_pages={contract.kv_pages} but "
                         f"init_paged_cache() returns {sorted(pools)}")
    if contract.kv_pages and contract.lane_layers is not None \
            and pools["k"].shape[0] != contract.lane_layers:
        raise ValueError(
            f"{said}: lane_layers={contract.lane_layers}, but the ``k`` "
            f"pool init_paged_cache() returns holds {pools['k'].shape[0]} "
            f"layers")
    declared = set(contract.work_counters) | set(contract.work_levels)
    returned = set()
    for field, work, args in (
            ("chunk_work", contract.chunk_work,
             (0, chunk, page_size, ring, layers)),
            ("block_work", contract.block_work, ([(1, 1)], ring, layers))):
        names = set(work(*args)) if work else set()
        if names - declared:
            raise ValueError(
                f"{said}: {field} returns {sorted(names - declared)}, in "
                f"neither work_counters nor work_levels")
        returned |= names
    if set(contract.work_counters) - returned:
        raise ValueError(
            f"{said}: work_counters names "
            f"{sorted(set(contract.work_counters) - returned)}, which "
            f"neither chunk_work nor block_work returns")
