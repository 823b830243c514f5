"""Host spans and commit hooks the benchmark installs around public calls.

Each wrapped call runs inside ``jax.profiler.TraceAnnotation(<name>)``, so
a traced run shows on the host timeline what the service was doing while
the device sat idle.  The wrappers are installed from this file only; a
name that no longer exists in the program raises here, loudly, and never
reads as zero.  ``StreamEngine.drain`` is also hooked to record each
commit: its time, the engine's stats and, when asked, the committed view.
"""

from __future__ import annotations

import functools
import importlib
import threading
import time

import jax

SPANS = (
    ("repro.core.stream", "StreamEngine", "submit"),
    ("repro.core.stream", "StreamEngine", "poll"),
    ("repro.graph.dynamic", "DynamicGraph", "apply_batch"),
    ("repro.core.stream", None, "build_host_problem"),
    ("repro.ingest.incremental_knn", "DeviceIngestor", "select"),
    ("repro.serving.lp_service", "LPService", "pump"),
)


def span_name(cls: str | None, name: str) -> str:
    return f"{cls}.{name}" if cls else name


class Hooks:
    """Installed wrappers and what they recorded."""

    def __init__(self, keep_views: bool):
        self.keep_views = keep_views
        self.commits: list[tuple[float, int, object]] = []  # (time, commit id, stats)
        self.views: dict[int, object] = {}
        self.selects: list[tuple[float, int]] = []  # (time, rows) per ingest select
        self.lock = threading.Lock()
        self._undo: list[tuple[object, str, object]] = []

    def install(self) -> "Hooks":
        for mod, cls, name in SPANS:
            owner = importlib.import_module(mod)
            if cls is not None:
                owner = getattr(owner, cls)
            orig = getattr(owner, name)  # AttributeError: the span's call is gone
            label = span_name(cls, name)

            def wrap(fn, label=label):
                @functools.wraps(fn)
                def inner(*a, **kw):
                    if label == "DeviceIngestor.select":
                        self.selects.append((time.perf_counter(), len(a[2])))
                    with jax.profiler.TraceAnnotation(label):
                        return fn(*a, **kw)
                return inner

            setattr(owner, name, wrap(orig))
            self._undo.append((owner, name, orig))
        stream = importlib.import_module("repro.core.stream").StreamEngine
        drain = stream.drain
        hooks = self

        @functools.wraps(drain)
        def drain_hook(engine, *a, **kw):
            st = drain(engine, *a, **kw)
            if st is not None:
                now = time.perf_counter()
                view = engine.committed_view()
                with hooks.lock:
                    hooks.commits.append((now, view.commit_id, st))
                    if hooks.keep_views:
                        hooks.views[view.commit_id] = view
            return st

        stream.drain = drain_hook
        self._undo.append((stream, "drain", drain))
        return self

    def uninstall(self) -> None:
        for owner, name, orig in reversed(self._undo):
            setattr(owner, name, orig)
        self._undo.clear()
