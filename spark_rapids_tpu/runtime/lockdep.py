"""Runtime lock-order witness — the dynamic half of the concurrency
soundness tier (docs/analysis.md#concurrency-invariants).

`install()` (armed by ``SPARK_RAPIDS_TPU_LOCKDEP=1`` — tests/conftest
for tier-1, when the variable is exported around pytest) monkeypatches
the ``threading.Lock``/``RLock`` factories so every lock CONSTRUCTED
from engine code is wrapped in a tracing proxy. Like kernel lockdep,
locks are bucketed into CLASSES by construction site (``path:line`` —
every ``LruDict`` instance's lock is one class), and each successful
acquire records the per-thread held-set → acquired edge into one
observed-order graph. The first edge that closes a cycle raises
``LockOrderViolation`` with both edges' capture stacks — a deadlock
certificate from a run that did NOT deadlock (witnessing A→B and B→A
needs only unlucky interleaving once, an actual deadlock needs it
twice at the same instant).

The vocabulary is SHARED with the static linter
(tools/lint_concurrency.py): `compare_to_static()` maps each observed
site-keyed edge through the linter's lock table (construction site →
``module:Class.attr`` name) and reports any dynamic edge the static
graph missed — the linter's interprocedural resolution is empirically
audited by every armed run. Edges touching a lock constructed at a
site the linter does not model (a local lock in a test helper) are
reported as `unmapped`, not divergence.

Same-class self-edges are skipped, mirroring the static tool: RLock
reentrancy on one instance is legal and a class-keyed self-edge cannot
distinguish it from a two-instance inversion. ``Condition`` wrappers
work unmodified: the proxy implements ``_is_owned``/``_release_save``/
``_acquire_restore``, so a ``wait()`` correctly drops the lock from
the held-set and re-enters it on wakeup.

The witness costs one dict/list touch per acquire — fine for tests and
soaks, not meant for production serving (hence the env gate).
"""
from __future__ import annotations

import os
import sys
import threading
import traceback
from typing import Dict, List, Optional, Set, Tuple

__all__ = ["LockOrderViolation", "install", "uninstall", "active",
           "reset", "snapshot", "compare_to_static", "certify"]

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ROOT = os.path.dirname(_PKG_DIR)


class LockOrderViolation(RuntimeError):
    """Two lock classes acquired in both orders — a potential deadlock,
    raised at the acquire that closed the cycle."""


def _stack_summary(skip: int = 3, limit: int = 8) -> str:
    frames = traceback.extract_stack()[:-skip]
    return "".join(traceback.format_list(frames[-limit:]))


class _Witness:
    """The observed-order graph. One global instance backs install();
    tests construct private ones to exercise cycles without poisoning
    the session graph."""

    def __init__(self):
        # a REAL lock (created before any patching) guarding the graph;
        # strictly leaf — nothing is acquired while it is held
        self._mu = threading.Lock()
        self._tls = threading.local()
        # (src_site, dst_site) -> (stack_at_first_observation, count)
        self._edges: Dict[Tuple[str, str], List] = {}
        self._adj: Dict[str, Set[str]] = {}
        self._cycles: List[str] = []

    def _held(self) -> List:
        h = getattr(self._tls, "held", None)
        if h is None:
            h = self._tls.held = []
        return h                           # [ [id(lock), site, count] ]

    # -- bookkeeping ----------------------------------------------------------

    def note_acquire(self, lock: "_TracedLock", count: int = 1) -> None:
        held = self._held()
        ident = id(lock)
        for ent in held:
            if ent[0] == ident:
                ent[2] += count            # reentrant re-acquire: no edge
                return
        new_edges = []
        for ent in held:
            if ent[1] != lock._site:       # same-class policy (docstring)
                new_edges.append((ent[1], lock._site))
        held.append([ident, lock._site, count])
        if not new_edges:
            return
        stack = None
        cycle_msg = None
        with self._mu:
            for edge in new_edges:
                rec = self._edges.get(edge)
                if rec is not None:
                    rec[1] += 1
                    continue
                if stack is None:
                    stack = _stack_summary()
                self._edges[edge] = [stack, 1]
                self._adj.setdefault(edge[0], set()).add(edge[1])
                path = self._find_path(edge[1], edge[0])
                if path is not None:
                    cycle = [edge[0]] + path
                    back = self._edges.get((edge[1], path[1] if
                                            len(path) > 1 else edge[0]))
                    cycle_msg = (
                        "lock-order cycle observed: "
                        + " -> ".join(cycle)
                        + f"\nnew edge {edge[0]} -> {edge[1]} "
                        f"acquired at:\n{stack}"
                        + (f"\nreverse path first observed at:\n{back[0]}"
                           if back else ""))
                    self._cycles.append(" -> ".join(cycle))
        if cycle_msg is not None:
            raise LockOrderViolation(cycle_msg)

    def _find_path(self, src: str, dst: str) -> Optional[List[str]]:
        """DFS path src -> ... -> dst in the observed graph (caller
        holds self._mu)."""
        stack = [(src, [src])]
        seen = {src}
        while stack:
            node, path = stack.pop()
            for nxt in self._adj.get(node, ()):
                if nxt == dst:
                    return path + [nxt]
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append((nxt, path + [nxt]))
        return None

    def note_release(self, lock: "_TracedLock") -> None:
        held = self._held()
        ident = id(lock)
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] == ident:
                held[i][2] -= 1
                if held[i][2] <= 0:
                    del held[i]
                return

    def drop_all(self, lock: "_TracedLock") -> int:
        """Forget every held entry for this instance (Condition.wait's
        _release_save); returns the recursion count to restore."""
        held = self._held()
        ident = id(lock)
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] == ident:
                count = held[i][2]
                del held[i]
                return count
        return 1

    # -- reporting ------------------------------------------------------------

    def edges(self) -> Dict[Tuple[str, str], int]:
        with self._mu:
            return {e: rec[1] for e, rec in self._edges.items()}

    def cycles(self) -> List[str]:
        with self._mu:
            return list(self._cycles)

    def reset(self) -> None:
        with self._mu:
            self._edges.clear()
            self._adj.clear()
            self._cycles.clear()


_witness = _Witness()


class _TracedLock:
    """Tracing proxy over a real Lock/RLock. Identity (its lock CLASS)
    is the construction site. Implements the Condition protocol so
    ``threading.Condition(traced_lock)`` keeps the held-set honest
    across wait/notify."""

    __slots__ = ("_inner", "_site", "_wit")

    def __init__(self, inner, site: str, wit: Optional[_Witness] = None):
        self._inner = inner
        self._site = site
        self._wit = wit if wit is not None else _witness

    def acquire(self, blocking: bool = True, timeout: float = -1):
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            try:
                self._wit.note_acquire(self)
            except LockOrderViolation:
                self._inner.release()
                self._wit.note_release(self)
                raise
        return ok

    def release(self):
        self._inner.release()
        self._wit.note_release(self)

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        return self._inner.locked()

    # -- Condition protocol ---------------------------------------------------

    def _is_owned(self):
        inner = self._inner
        if hasattr(inner, "_is_owned"):
            return inner._is_owned()
        # plain Lock: CPython's own approximation (threading.Condition
        # does exactly this for primitive locks)
        if inner.acquire(False):
            inner.release()
            return False
        return True

    def _release_save(self):
        count = self._wit.drop_all(self)
        if hasattr(self._inner, "_release_save"):
            state = self._inner._release_save()
        else:
            self._inner.release()
            state = None
        return (state, count)

    def _acquire_restore(self, saved):
        state, count = saved
        if hasattr(self._inner, "_acquire_restore"):
            self._inner._acquire_restore(state)
        else:
            self._inner.acquire()
        # re-entering after wait is a real ordering event: record edges
        # from whatever else this thread still holds
        self._wit.note_acquire(self, count)

    def __repr__(self):
        return f"<_TracedLock {self._site} over {self._inner!r}>"


# ---- installation -----------------------------------------------------------

_real_lock = None
_real_rlock = None
# Guards the factory swap itself; bound at import time, before install()
# can ever patch the factory, so it is always a plain stdlib lock.
_install_lock = threading.Lock()


def _caller_site() -> Optional[str]:
    """Construction site of the lock being created: the nearest caller
    frame inside the engine package (None for stdlib/test/bench
    callers — those get real, untraced locks)."""
    here = os.path.abspath(__file__)
    f = sys._getframe(2)
    while f is not None:
        # normalize: a relative sys.path entry (a script's insert of ".")
        # leaves "/repo/./pkg/..." in co_filename, defeating the
        # prefix check below
        fn = os.path.abspath(f.f_code.co_filename)
        if fn != here:
            if fn.startswith(_PKG_DIR + os.sep):
                rel = os.path.relpath(fn, _ROOT).replace(os.sep, "/")
                return f"{rel}:{f.f_lineno}"
            return None
        f = f.f_back
    return None


def _lock_factory():
    site = _caller_site()
    if site is None:
        return _real_lock()
    return _TracedLock(_real_lock(), site)


def _rlock_factory():
    site = _caller_site()
    if site is None:
        return _real_rlock()
    return _TracedLock(_real_rlock(), site)


def active() -> bool:
    return _real_lock is not None


def install() -> None:
    """Patch the threading lock factories. Idempotent. Must run BEFORE
    the engine modules are imported so module-level locks (serving/
    cache._digest_lock, plan/stats._default_lock, ...) get wrapped."""
    global _real_lock, _real_rlock
    with _install_lock:
        if _real_lock is not None:
            return
        _real_lock = threading.Lock
        _real_rlock = threading.RLock
        threading.Lock = _lock_factory
        threading.RLock = _rlock_factory


def uninstall() -> None:
    """Restore the real factories. Locks already wrapped keep tracing
    (they are self-contained proxies)."""
    global _real_lock, _real_rlock
    with _install_lock:
        if _real_lock is None:
            return
        threading.Lock = _real_lock
        threading.RLock = _real_rlock
        _real_lock = _real_rlock = None


def reset() -> None:
    _witness.reset()


# ---- static comparison ------------------------------------------------------

def _load_static_graph() -> Dict:
    import importlib.util
    path = os.path.join(_ROOT, "tools", "lint_concurrency.py")
    spec = importlib.util.spec_from_file_location("_lint_concurrency", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # the linter's dataclasses need it
    spec.loader.exec_module(mod)
    return mod.build_graph_json(repo_root=_ROOT)


def snapshot() -> Dict:
    """Raw witness state: site-keyed edges with observation counts,
    plus any cycles recorded before their raise unwound."""
    edges = _witness.edges()
    return {"edges": {f"{a} -> {b}": n for (a, b), n in
                      sorted(edges.items())},
            "cycles": _witness.cycles()}


def compare_to_static(graph: Optional[Dict] = None) -> Dict:
    """Map observed edges through the static lock table and report
    divergence. Returns {"observed": n, "mapped": [...], "missing":
    [...], "unmapped": [...]} where `missing` lists dynamic edges
    (as 'A -> B' lock-name strings) absent from the static graph —
    the linter's resolution gap, which fails the armed suite/soak."""
    if graph is None:
        graph = _load_static_graph()
    site_to_name = {site: name for name, site in graph["locks"].items()}
    static_edges = {tuple(e) for e in graph["edges"]}
    observed = _witness.edges()
    mapped, missing, unmapped = [], [], []
    seen: Set[Tuple[str, str]] = set()
    for (a_site, b_site), _count in sorted(observed.items()):
        a = site_to_name.get(a_site)
        b = site_to_name.get(b_site)
        if a is None or b is None:
            unmapped.append(f"{a_site} -> {b_site}")
            continue
        if a == b or (a, b) in seen:
            continue
        seen.add((a, b))
        if (a, b) in static_edges:
            mapped.append(f"{a} -> {b}")
        else:
            missing.append(f"{a} -> {b}")
    return {"observed": len(observed), "mapped": mapped,
            "missing": missing, "unmapped": unmapped}


def certify(graph: Optional[Dict] = None) -> Dict:
    """The armed run's verdict: observed cycles + static divergence in
    one report (what conftest's sessionfinish and the chaos soak
    assert on)."""
    rep = compare_to_static(graph)
    rep["cycles"] = _witness.cycles()
    rep["ok"] = not rep["cycles"] and not rep["missing"]
    return rep
