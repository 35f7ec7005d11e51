"""Async-dispatch pipeline bounding.

On the virtual multi-device CPU mesh an unbounded pipeline of sharded
programs starves XLA:CPU's shared thread pool: devices of one in-flight
program occupy the threads another program's collective rendezvous is
waiting for, and past the rendezvous timeout the whole process
CHECK-aborts ("Fatal Python error: Aborted" at a harmless-looking
dispatch).  ``DispatchWindow`` bounds the depth as a ROLLING window —
past N tracked arrays, each push blocks on the OLDEST (its completion
implies every earlier dependent dispatch ran, and ~N newer programs
stay in flight, so there is no pipeline bubble).

The bound is in PROGRAMS.  XLA:CPU admits 32 computations in flight a
device and BLOCKS the dispatching thread at the 33rd — in the middle of
a multi-device launch, some devices' parts enqueued and waiting at a
rendezvous for the parts the blocked thread has yet to enqueue — and on
an oversubscribed host nothing older retires to free a slot: a hang,
then the abort above.  A loop whose step is more than one program (the
word2vec async pair: gradients, then apply) says so (``programs``), and
its window holds that many fewer steps: 16 programs either way.

The ``"auto"`` policy applies the bound only on the cpu backend: a real
TPU chip runs one program at a time and needs no bound.  Shared by
the word2vec train loops (which push the one result of a step that is
not donated into the next: its own error sum), the LR train loop, and
anything else that queues device results without fetching them.
"""

from __future__ import annotations

from typing import Optional, Union

import jax

AUTO_BOUND = 16


class DispatchWindow:
    def __init__(self, bound: Union[str, int, None] = "auto",
                 programs: int = 1):
        """``bound`` programs in flight (``"auto"``: the backend policy
        above; ``None``: no bound), ``programs`` of them a pushed value."""
        if bound == "auto":
            bound = AUTO_BOUND if jax.default_backend() == "cpu" else None
        self._bound: Optional[int] = \
            bound if bound is None else max(1, bound // programs)
        self._window: list = []

    def push(self, x) -> None:
        """Track one in-flight device value; block on the oldest tracked
        value once more than ``bound`` are outstanding."""
        if self._bound is None:
            return
        self._window.append(x)
        if len(self._window) > self._bound:
            jax.block_until_ready(self._window.pop(0))

    def clear(self) -> None:
        self._window.clear()


def resolve_dispatch_bound(depth: Union[str, int, None],
                           pipelined: bool = False) -> Union[str, int, None]:
    """Resolve the ``[worker] dispatch_depth`` knob into a
    ``DispatchWindow`` bound.

    ``"auto"`` keeps the backend policy above — EXCEPT when the input
    pipeline is on: with prefetched batches the consumer can dispatch
    as fast as it renders nothing, so without a finite watermark async
    dispatch outruns HBM (every in-flight program pins its donated
    state copy + inputs).  Pipelined ``"auto"`` therefore bounds every
    backend at ``AUTO_BOUND``.  An explicit integer (or ``0`` meaning
    unbounded) always wins.
    """
    if depth == "auto" or depth is None:
        return AUTO_BOUND if pipelined else "auto"
    depth = int(depth)
    return None if depth <= 0 else depth
