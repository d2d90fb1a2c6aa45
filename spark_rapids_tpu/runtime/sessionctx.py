"""Serving-session identity context.

The serving layer (serving/scheduler.py, docs/serving.md) multiplexes N
tenant sessions over a small pool of dispatcher worker threads, and the
degraded CPU tier replays work on whatever thread hit the breaker — so
"which tenant does this work belong to" can no longer be answered by
thread identity. This module is the one place that question is asked:

- `session_scope(sid)` installs a session id for the dynamic extent on
  the CURRENT thread (re-entrant; the innermost scope wins). The serving
  dispatcher wraps every job execution in it.
- `current_session_id()` returns it (None outside any scope).
- `session_key()` is the budget/window key the health monitor uses
  (runtime/health.py): the explicit session id when set, else a
  thread-derived fallback — so unscoped callers keep the historical
  per-thread isolation, while scoped work is accounted to its TENANT
  even when several tenants share one worker thread (or one tenant
  spans several).

- `request_scope(n)` / `current_request()` do the same for the REQUEST
  number the program's spans carry (utils/tracing.py): the scheduler
  numbers its tickets and scopes the worker's execution, a direct
  `PlanExecutor.execute` scopes its own counter's next number; -1
  outside any scope.

Kept deliberately tiny and dependency-free: runtime/health.py must be
importable without the serving package.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional

_ctx = threading.local()


def current_session_id() -> Optional[str]:
    """The innermost session id scoped on this thread, or None."""
    stack = getattr(_ctx, "stack", None)
    return stack[-1] if stack else None


def session_key() -> str:
    """Accounting key for per-session state (retry budgets, sticky
    windows): the scoped session id, falling back to thread identity so
    unscoped execution keeps per-thread isolation."""
    sid = current_session_id()
    return sid if sid is not None else f"thread:{threading.get_ident()}"


@contextlib.contextmanager
def session_scope(session_id: str) -> Iterator[str]:
    """Attribute the dynamic extent to `session_id` on this thread."""
    stack = getattr(_ctx, "stack", None)
    if stack is None:
        stack = _ctx.stack = []
    stack.append(str(session_id))
    try:
        yield session_id
    finally:
        stack.pop()


def current_request() -> int:
    """The innermost request number scoped on this thread, or -1."""
    stack = getattr(_ctx, "requests", None)
    return stack[-1] if stack else -1


@contextlib.contextmanager
def request_scope(request: int) -> Iterator[int]:
    """Attribute the dynamic extent to request `request` on this thread."""
    stack = getattr(_ctx, "requests", None)
    if stack is None:
        stack = _ctx.requests = []
    stack.append(int(request))
    try:
        yield request
    finally:
        stack.pop()
