"""StateManager (paper §4.4): lifecycle + consistent updates + atomic
rollbacks for per-model ModelStates.

Atomicity note: JAX states are immutable pytrees; every update is
replace-on-success, so a failed processor call can never leave a state
half-mutated — this *is* the paper's atomic-rollback requirement, obtained
structurally rather than via locking.  The ``_lock`` guards the *registry*
itself: every read-modify-write (``free_rows``, ``maybe_defragment``) holds
it end to end, so a concurrent ``update`` can neither interleave between
the read and the write-back nor be silently overwritten by a stale state.

Slot-level continuous batching: a serving session keys ONE batch-B state
per model (``model/session_id``); individual batch rows are *slots* that
are freed (``free_rows``) when a request finishes and re-filled by a
catch-up prefill when a new request is admitted.  ``create`` optionally
records the state's axes pytree (what ``make_state`` returns) so
``free_rows`` can wipe recurrent per-row carries exactly (named
``"batch"`` axes), not heuristically, and an admission can run its
forward over the admitted rows alone (``axes``).

Paged states (``PagedModelState``) free and account capacity in BLOCKS:
``free_rows`` returns a retired row's blocks to the pool in O(1) and
defragmentation is structurally unnecessary (rows cannot leak holes into
each other), so ``maybe_defragment`` is a no-op for them.
"""
from __future__ import annotations

import threading
from typing import Any, Dict

import jax
import numpy as np

from ..models.kv_cache import (PagedModelState, blocks_in_use, fragmentation, defragment, free_rows as _free_rows)


class StateManager:
    def __init__(self, defrag_threshold: float = 0.5):
        self._states: Dict[str, Any] = {}
        self._axes: Dict[str, Any] = {}
        self._shardings: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self.defrag_threshold = defrag_threshold
        self.defrag_count = 0

    def create(self, state_id: str, state, axes: Any = None,
               sharding: Any = None):
        """``sharding`` (a NamedSharding pytree from Placement) places the
        KV block pools / session buffers explicitly on creation; it is
        remembered so mesh-aware callers can re-place a rebuilt state.
        None (the trivial placement) leaves the state exactly where the
        allocating op produced it — the legacy single-device path."""
        if sharding is not None:
            state = jax.device_put(state, sharding)
        with self._lock:
            self._states[state_id] = state
            if axes is not None:
                self._axes[state_id] = axes
            else:
                self._axes.pop(state_id, None)
            if sharding is not None:
                self._shardings[state_id] = sharding
            else:
                self._shardings.pop(state_id, None)

    def sharding(self, state_id: str):
        """The NamedSharding tree a state was created with (None on the
        trivial placement)."""
        with self._lock:
            return self._shardings.get(state_id)

    def get(self, state_id: str):
        with self._lock:
            return self._states[state_id]

    def axes(self, state_id: str):
        """The axes pytree a state was created with (None if none)."""
        with self._lock:
            return self._axes.get(state_id)

    def exists(self, state_id: str) -> bool:
        """Lazy chain membership: a model outside every live slot's chain
        never materializes a session state at all."""
        with self._lock:
            return state_id in self._states

    def update(self, state_id: str, state):
        with self._lock:
            self._states[state_id] = state

    def checkout(self, state_ids):
        """Atomically REMOVE and return several states (fused-cycle entry):
        the fused executor donates the state buffers to its jitted program,
        and a donated buffer must have no surviving reference — popping the
        registry entries guarantees no concurrent reader can touch the
        invalidated arrays mid-cycle.  Pair with ``commit`` (the program's
        outputs on success, the originals on a trace-time failure)."""
        with self._lock:
            return [self._states.pop(s) for s in state_ids]

    def commit(self, state_ids, states):
        """Write back states taken by ``checkout`` (replace-on-success —
        the same atomicity contract as ``update``, for many states)."""
        with self._lock:
            for s, st in zip(state_ids, states):
                self._states[s] = st

    def release(self, state_id: str):
        with self._lock:
            self._states.pop(state_id, None)
            self._axes.pop(state_id, None)
            self._shardings.pop(state_id, None)

    def release_request(self, request_id: str):
        """GC every model's state for a finished request/session."""
        with self._lock:
            for k in [k for k in self._states if k.endswith("/" + request_id)]:
                self._states.pop(k)
                self._axes.pop(k, None)
                self._shardings.pop(k, None)

    def free_rows(self, state_id: str, rows: np.ndarray):
        """Retire slot rows of a session state atomically: the read, the
        per-row release (paged: O(1) block return; contiguous: logical mask
        release + exact recurrent-carry wipe), and the write-back all
        happen under the registry lock."""
        with self._lock:
            st = self._states[state_id]
            axes = self._axes.get(state_id)
            self._states[state_id] = _free_rows(
                st, rows, axes.layers if axes is not None else None)

    def maybe_defragment(self, state_id: str, force: bool = False) -> bool:
        """Beyond-paper: compact masked holes when fragmentation is high
        (or unconditionally when ``force``, e.g. on capacity pressure).
        Atomic read-modify-write; no-op for paged states (per-row block
        tables cannot fragment across slots)."""
        with self._lock:
            st = self._states[state_id]
            if isinstance(st, PagedModelState):
                return False
            frag = float(fragmentation(st))
            if force or frag > self.defrag_threshold:
                self._states[state_id] = defragment(st)
                self.defrag_count += 1
                return True
            return False

    def lengths(self, state_id: str) -> np.ndarray:
        with self._lock:
            return np.asarray(self._states[state_id].length)

    def row_footprint(self, state_id: str, row: int) -> int:
        """Physical cache entries held by ONE batch row: allocated blocks
        × block size for paged states, the row's cached length for
        contiguous ones.  0 for a missing state — the O(chain) admission
        invariant ('pool models outside the assigned chain hold zero
        rows/blocks for a slot') is asserted against this."""
        with self._lock:
            st = self._states.get(state_id)
        if st is None:
            return 0
        if isinstance(st, PagedModelState):
            return int(np.asarray(st.num_blocks)[row]) * st.block_size
        return int(np.asarray(st.length)[row])

    def capacity_used(self, state_id: str) -> int:
        """Physical occupancy: shared-pointer height for contiguous states,
        in-use pool slots (blocks * block_size) for paged ones."""
        with self._lock:
            st = self._states[state_id]
        if isinstance(st, PagedModelState):
            return int(blocks_in_use(st)) * st.block_size
        return int(st.write_ptr)

    @staticmethod
    def key(model: str, request_id: str) -> str:
        return f"{model}/{request_id}"
