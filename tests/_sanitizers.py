"""Runtime concurrency sanitizers — the dynamic half of graftlint.

Static rules (``python -m bigdl_tpu.analysis``) catch lock misuse that is
visible in the source; this module catches the ordering bugs that are not.
Two checks run around every test (autouse fixtures, wired up in
``conftest.py``):

**Lock-order sanitizer.**  ``threading.Lock``/``threading.RLock`` are
replaced with factories returning thin wrappers that delegate every
operation to a real lock while recording, per thread, the stack of locks
currently held.  Acquiring lock B while holding lock A adds the edge
``A -> B`` to a process-global lock-order graph.  A cycle in that graph
means two threads can interleave into a deadlock *even if the run at hand
got lucky* — the classic ABBA hang is reported from a green run.  Edges
are cleared per test; a cycle fails that test with both acquisition sites
in the message.

**Leaked-thread sanitizer.**  Library threads are uniformly named
(``bigdl-*``, ``pipeline-*``, ``ckpt-writer*``, ``host-prefetch``).  Each
test snapshots live threads on entry; on exit, any *new* library-named
thread still alive after a short join grace fails the test.  A component
that forgets to join its worker gets caught by the test that leaked it,
not by a flaky timeout three modules later.

Wrappers mirror the real lock API closely enough for
``threading.Condition`` (``_release_save``/``_acquire_restore``/
``_is_owned`` delegation for RLocks), ``_at_fork_reinit``, and refuse
pickling exactly like real locks.  Locks created *before*
:func:`install` runs (e.g. jax internals — conftest installs after the
jax import on purpose) stay untracked real locks.

Set ``BIGDL_TPU_NO_SANITIZE=1`` to turn both checks off — e.g. when
bisecting whether the sanitizer itself perturbs a timing-sensitive test.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, List, Optional, Tuple

import pytest

DISABLE_ENV = "BIGDL_TPU_NO_SANITIZE"

_real_lock_factory = threading.Lock
_real_rlock_factory = threading.RLock

_installed = False


def _disabled() -> bool:
    return os.environ.get(DISABLE_ENV, "") not in ("", "0")


def _caller_site() -> str:
    """``path/file.py:lineno`` of the nearest frame outside this module."""
    f = sys._getframe(1)
    while f is not None and f.f_code.co_filename == __file__:
        f = f.f_back
    if f is None:
        return "?"
    parts = f.f_code.co_filename.replace("\\", "/").split("/")
    return "/".join(parts[-2:]) + f":{f.f_lineno}"


# -- the lock-order graph -----------------------------------------------------

class LockGraph:
    """Central bookkeeping: per-thread held-lock stacks plus the
    acquired-while-holding edge set.  Guarded by a raw (untracked)
    mutex; the blocking inner ``acquire`` never happens under it."""

    def __init__(self):
        self._mu = _real_lock_factory()
        self._serial = 0
        # thread ident -> stack of [serial, recursion count, name, site]
        self.held: Dict[int, List[list]] = {}
        # (held serial, acquired serial) ->
        #     (held name, acquired name, held site, acquired site, thread)
        self.edges: Dict[Tuple[int, int], Tuple[str, str, str, str, str]] = {}

    def next_serial(self) -> int:
        with self._mu:
            self._serial += 1
            return self._serial

    def note_acquire(self, serial: int, name: str, site: str,
                     count: int = 1) -> None:
        tid = threading.get_ident()
        with self._mu:
            stack = self.held.setdefault(tid, [])
            for entry in stack:
                if entry[0] == serial:  # RLock recursion: no new edge
                    entry[1] += count
                    return
            for prev in stack:
                key = (prev[0], serial)
                if key not in self.edges:
                    self.edges[key] = (prev[2], name, prev[3], site,
                                       threading.current_thread().name)
            stack.append([serial, count, name, site])

    def note_release(self, serial: int) -> None:
        tid = threading.get_ident()
        with self._mu:
            if self._pop(self.held.get(tid), serial, 1) is not None:
                return
            # plain Locks may be released by a thread other than the
            # acquirer (handoff protocols); find the holder and pop there
            for stack in self.held.values():
                if self._pop(stack, serial, 1) is not None:
                    return

    def note_release_all(self, serial: int) -> int:
        """Fully drop ``serial`` from the calling thread's stack and
        return the recursion count (RLock ``_release_save``)."""
        with self._mu:
            n = self._pop(self.held.get(threading.get_ident()), serial,
                          None)
            return n if n is not None else 1

    @staticmethod
    def _pop(stack: Optional[list], serial: int,
             count: Optional[int]) -> Optional[int]:
        if not stack:
            return None
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] == serial:
                if count is None or stack[i][1] <= count:
                    n = stack[i][1]
                    del stack[i]
                    return n
                stack[i][1] -= count
                return count
        return None

    def reset_edges(self) -> None:
        with self._mu:
            self.edges.clear()

    def snapshot_edges(self):
        with self._mu:
            return dict(self.edges)

    def _reinit_after_fork(self) -> None:
        # a forked child inherits the parent's bookkeeping mid-flight
        # (possibly including a held _mu); start clean
        self._mu = _real_lock_factory()
        self.held = {}
        self.edges = {}


def find_cycle(edges) -> Optional[List[int]]:
    """First lock-order cycle in ``edges`` as ``[a, b, ..., a]``, or
    None.  Iterative three-color DFS."""
    adj: Dict[int, List[int]] = {}
    for a, b in edges:
        adj.setdefault(a, []).append(b)
    color: Dict[int, int] = {}
    for root in adj:
        if color.get(root):
            continue
        color[root] = 1
        path = [root]
        stack = [(root, iter(adj.get(root, ())))]
        while stack:
            node, it = stack[-1]
            advanced = False
            for nxt in it:
                c = color.get(nxt)
                if c == 1:
                    return path[path.index(nxt):] + [nxt]
                if c is None:
                    color[nxt] = 1
                    path.append(nxt)
                    stack.append((nxt, iter(adj.get(nxt, ()))))
                    advanced = True
                    break
            if not advanced:
                color[node] = 2
                path.pop()
                stack.pop()
    return None


def format_cycle(cycle: List[int], edges) -> str:
    lines = ["lock-order cycle (potential deadlock):"]
    for a, b in zip(cycle, cycle[1:]):
        ha, hb, sa, sb, thread = edges[(a, b)]
        lines.append(f"  {ha} (held, acquired at {sa}) -> {hb} "
                     f"(acquired at {sb}) in thread '{thread}'")
    lines.append("two threads taking these paths concurrently can "
                 "deadlock even though this run did not")
    return "\n".join(lines)


_GRAPH = LockGraph()


# -- lock wrappers ------------------------------------------------------------

class _TrackedLock:
    """Delegating wrapper around a real ``threading.Lock``."""

    _kind = "Lock"
    __slots__ = ("_inner", "_serial", "_name", "_graph")

    def __init__(self, inner, graph: LockGraph = None):
        self._inner = inner
        self._graph = graph if graph is not None else _GRAPH
        self._serial = self._graph.next_serial()
        self._name = f"{self._kind}#{self._serial}({_caller_site()})"

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._inner.acquire(blocking, timeout)
        if got:
            self._graph.note_acquire(self._serial, self._name,
                                     _caller_site())
        return got

    def release(self) -> None:
        self._graph.note_release(self._serial)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self):
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    def _at_fork_reinit(self) -> None:
        self._inner._at_fork_reinit()

    def __reduce__(self):
        raise TypeError(f"cannot pickle '{type(self).__name__}' object")

    def __repr__(self) -> str:
        return f"<{self._name} wrapping {self._inner!r}>"


class _TrackedRLock(_TrackedLock):
    """Delegating wrapper around a real ``threading.RLock``; the
    ``_release_save``/``_acquire_restore``/``_is_owned`` trio keeps
    ``threading.Condition`` working (and the held-stack honest across
    ``Condition.wait``, which fully releases the lock)."""

    _kind = "RLock"
    __slots__ = ()

    def locked(self):  # RLock grew .locked() only in 3.12
        return self._inner.locked()

    def _is_owned(self) -> bool:
        return self._inner._is_owned()

    def _recursion_count(self) -> int:
        # multiprocessing.resource_tracker asks its RLock this (3.12.x)
        return self._inner._recursion_count()

    def _release_save(self):
        count = self._graph.note_release_all(self._serial)
        return (self._inner._release_save(), count)

    def _acquire_restore(self, state) -> None:
        inner_state, count = state
        self._inner._acquire_restore(inner_state)
        self._graph.note_acquire(self._serial, self._name, _caller_site(),
                                 count=count)


def _tracked_lock():
    return _TrackedLock(_real_lock_factory())


def _tracked_rlock():
    return _TrackedRLock(_real_rlock_factory())


def install() -> None:
    """Swap the ``threading.Lock``/``RLock`` factories for tracked ones.
    Idempotent; a no-op when ``BIGDL_TPU_NO_SANITIZE`` is set.  Call
    *after* importing jax — locks allocated before install stay real and
    untracked, which keeps foreign-runtime internals out of the graph."""
    global _installed
    if _installed or _disabled():
        return
    threading.Lock = _tracked_lock
    threading.RLock = _tracked_rlock
    if hasattr(os, "register_at_fork"):
        os.register_at_fork(after_in_child=_GRAPH._reinit_after_fork)
    _installed = True


# -- pytest fixtures (imported by conftest.py) --------------------------------

@pytest.fixture(autouse=True)
def _lock_order_sanitizer():
    if not _installed:
        yield
        return
    _GRAPH.reset_edges()
    yield
    edges = _GRAPH.snapshot_edges()
    cycle = find_cycle(edges)
    if cycle is not None:
        pytest.fail("graftlint sanitizer: " + format_cycle(cycle, edges),
                    pytrace=False)


_LIBRARY_THREAD_PREFIXES = ("bigdl-", "pipeline-", "ckpt-writer",
                            "host-prefetch")
_JOIN_GRACE_S = 3.0


def leaked_library_threads(before_idents):
    """Live library-named threads not in the ``before_idents`` snapshot."""
    return [t for t in threading.enumerate()
            if t.ident not in before_idents and t.is_alive()
            and t.name.startswith(_LIBRARY_THREAD_PREFIXES)]


@pytest.fixture(autouse=True)
def _leaked_thread_sanitizer():
    if _disabled():
        yield
        return
    before = {t.ident for t in threading.enumerate()}
    yield
    import time

    deadline = time.monotonic() + _JOIN_GRACE_S
    left = leaked_library_threads(before)
    for t in left:  # give orderly teardowns a moment to finish
        t.join(max(0.0, deadline - time.monotonic()))
    left = leaked_library_threads(before)
    if left:
        pytest.fail(
            "graftlint sanitizer: test leaked library threads: "
            + ", ".join(sorted(t.name for t in left))
            + " — join or daemonize them in the owning component's "
              "close()/teardown path", pytrace=False)
