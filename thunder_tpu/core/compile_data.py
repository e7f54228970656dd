"""Compile-option plumbing: ad-hoc, self-documenting flags queried by passes.

Reference parity: ``thunder/core/compile_data.py:57-87`` —
``thunder.jit(fn, **compile_options)`` accepts free-form options; passes query
them lazily via ``get_compile_option(name, description)``, and every query
self-registers so the driver can report which options were used vs silently
ignored (``thunder/__init__.py:980-1015`` ``last_compile_options``).
"""

from __future__ import annotations

from contextvars import ContextVar
from typing import Any

_compile_ctx: ContextVar = ContextVar("thunder_tpu_compile_ctx", default=None)


class CompileContext:
    """Holds the options passed to ``jit`` plus the registry of queries made
    by passes during compilation."""

    __slots__ = ("options", "queried")

    def __init__(self, options: dict[str, Any]):
        self.options = dict(options)
        self.queried: dict[str, str] = {}  # name -> description


class compile_context:
    def __init__(self, ctx: CompileContext):
        self.ctx = ctx
        self.token = None

    def __enter__(self):
        self.token = _compile_ctx.set(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        _compile_ctx.reset(self.token)
        return False


def get_compile_option(name: str, description: str, default: Any = None) -> Any:
    """Query a compile option from inside a pass/executor. The query is
    recorded (with its docstring) so unknown/unused options are reportable."""
    ctx = _compile_ctx.get()
    if ctx is None:
        return default
    ctx.queried[name] = description
    return ctx.options.get(name, default)


def used_and_unused_options(ctx: CompileContext) -> tuple[dict, set]:
    """(queried options with descriptions, passed-but-never-queried names)."""
    unused = set(ctx.options) - set(ctx.queried)
    return dict(ctx.queried), unused
