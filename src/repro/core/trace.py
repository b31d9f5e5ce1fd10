"""Named host spans on the profiler's clock.

``span(name, **args)`` marks a stretch of host work with a profiler trace
event: a ``TraceMe``, the class ``jax.profiler.TraceAnnotation`` is,
taken from jaxlib so that a client or a numpy-backend server need not
import jax.  The event lands in the same trace as the device's op events,
so an idle gap on the device can be put down to the host span open across
it.  Spans record exactly while a profiler records (``jax.profiler.
start_trace``, or ``start_server`` and a remote capture: see
``docs/operations.md``); otherwise ``span`` returns one shared no-op and
nothing is kept.

Every span carries the flow id of the request it works for as ``flow=``.
The id is thread-local: ``flow(flow_id)`` sets it for a block, and
``bind(fn)`` wraps a thread's target or a pool task so that it runs under
the caller's id, which is how the id reaches the threads that work for the
request (executor workers, prefetchers, the scan's reader pool).  It is
not a ``contextvars`` variable: worker threads run inside a copied,
non-empty context then, where numpy's ufuncs look up their error state,
and a TPC-H Q1 COOK measured ~4 % slower on a CPU host that way.

Rules: a span never encloses a ``yield`` (a suspended generator may resume
on another thread); no span is opened per row, per group or inside a numpy
loop; a span's arguments are values already at hand.
"""

from __future__ import annotations

import contextlib
import threading

try:
    from jaxlib._profiler import TraceMe as _TraceMe
except ImportError:  # pragma: no cover - jax is needed only for the kernels; without it nothing records
    _TraceMe = None

__all__ = ["SPANS", "span", "flow", "bind"]

# every span name the program opens, from the request down to the morsel
SPANS = (
    "dacp.cook",
    "dacp.plan",
    "dacp.scan.part",
    "dacp.scan.batch",
    "dacp.scan.filter",
    "dacp.scan.wait",
    "dacp.morsel",
    "dacp.morsel.factorize",
    "dacp.morsel.encode",
    "dacp.morsel.launch",
    "dacp.morsel.sync",
    "dacp.morsel.fold",
    "dacp.agg.map",
    "dacp.merge",
    "dacp.frame.send",
    "dacp.frame.recv",
)

_local = threading.local()  # .flow: the flow id of the thread's current work


class _Off:
    """The span while no profiler records: enters and leaves, keeps nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def set_metadata(self, **args) -> None:
        pass


_OFF = _Off()


def span(name: str, **args):
    """A context manager timing ``name`` on the profiler's clock, with
    ``args`` and the current flow id as the event's stats.  ``set_metadata``
    on the entered span adds stats known only later (a new flow's id)."""
    if _TraceMe is None or not _TraceMe.is_enabled():
        return _OFF
    fid = args.pop("flow", None) or getattr(_local, "flow", None)
    if fid is not None:
        args["flow"] = fid
    return _TraceMe(name, **args)


@contextlib.contextmanager
def flow(flow_id: str):
    """Spans opened inside on this thread, and in threads and tasks
    :func:`bind` wraps inside, carry ``flow_id``."""
    prev = getattr(_local, "flow", None)
    _local.flow = flow_id
    try:
        yield
    finally:
        _local.flow = prev


def bind(fn):
    """``fn`` wrapped to run under the caller's flow id, on whatever thread
    calls it: a thread's target or a pool task that works for the caller's
    flow.  Outside a flow, ``fn`` itself."""
    fid = getattr(_local, "flow", None)
    if fid is None:
        return fn

    def run(*args, **kwargs):
        with flow(fid):
            return fn(*args, **kwargs)

    return run
