"""Persisted autotuning table: sweep winners keyed by deployment point.

One JSON file maps ``(kernel, engine, bucket, batch, backend, jax
version)`` to the schedule options (``strip``, ``tb_pack``) a measured
sweep picked — the software analogue of the paper's per-configuration
synthesis results, committed next to the code so every later session
boots with the tuned schedule instead of re-searching.

Staleness is structural, not advisory: backend and ``jax.__version__``
are *part of the key*, so entries recorded on a different backend or
against a different JAX simply never match (a lookup miss falls back to
the hand-picked defaults).  A ``schema`` field guards the file format
itself — an unknown schema refuses to load.

``repro.runtime.plan.get_plan`` consults :func:`lookup` when the caller
passed no explicit schedule option.  Resolution order:

1. env ``REPRO_TUNE_TABLE=off|0|none|disabled`` — table disabled, the
   hand-picked defaults apply exactly (wins over everything, including
   :func:`set_table`);
2. a table installed programmatically via :func:`set_table`;
3. env ``REPRO_TUNE_TABLE=<path>`` — explicit table file;
4. ``TUNE_TABLE.json`` at the repo root, if present.

Any load problem (missing file, corrupt JSON, wrong schema) silently
resolves to "no table" — a bad table must never break dispatch.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import pathlib
import threading
from typing import Optional

SCHEMA_VERSION = 1
ENV_VAR = "REPRO_TUNE_TABLE"
DEFAULT_TABLE_NAME = "TUNE_TABLE.json"
_OFF_VALUES = {"off", "0", "none", "disabled", "false"}


def entry_key(kernel: str, engine: str, bucket: tuple,
              batch_size: Optional[int], *, backend: Optional[str] = None,
              jax_version: Optional[str] = None) -> str:
    """Canonical string key of one tuning point.

    ``bucket`` is the per-pair length pair ``(Q, R)`` (char dims are a
    property of the kernel, not the point).  Backend and JAX version
    default to the running process's — the same call that records an
    entry is the one that can legitimately match it later.
    """
    if backend is None or jax_version is None:
        import jax
        backend = backend or jax.default_backend()
        jax_version = jax_version or jax.__version__
    b = "single" if batch_size is None else f"b{int(batch_size)}"
    return "|".join([kernel, engine, f"{int(bucket[0])}x{int(bucket[1])}",
                     b, backend, jax_version])


@dataclasses.dataclass
class TuningTable:
    """In-memory view of one table file (see module docstring)."""
    entries: dict = dataclasses.field(default_factory=dict)
    schema: int = SCHEMA_VERSION
    created: Optional[str] = None
    path: Optional[str] = None

    # -- persistence -------------------------------------------------------
    @classmethod
    def load(cls, path) -> "TuningTable":
        """Load a table file; raises on unreadable/foreign schema (the
        module-level :func:`lookup` catches and treats it as no table)."""
        path = str(path)
        with open(path) as f:
            raw = json.load(f)
        schema = raw.get("schema")
        if schema != SCHEMA_VERSION:
            raise ValueError(
                f"tuning table {path}: schema {schema!r} != "
                f"{SCHEMA_VERSION} (stale file; re-run scripts/autotune.py)")
        entries = raw.get("entries")
        if not isinstance(entries, dict):
            raise ValueError(f"tuning table {path}: no entries mapping")
        return cls(entries=dict(entries), schema=schema,
                   created=raw.get("created"), path=path)

    def save(self, path=None) -> str:
        path = str(path or self.path)
        if not path or path == "None":
            raise ValueError("TuningTable.save: no path")
        payload = {"schema": self.schema, "created": self.created,
                   "entries": self.entries}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        self.path = path
        return path

    # -- record / lookup ---------------------------------------------------
    def record(self, kernel: str, engine: str, bucket: tuple,
               batch_size: Optional[int], options: dict, **meta) -> str:
        """Store a sweep winner; ``meta`` (measured cells/sec, speedup,
        ...) rides along for reporting but is never read at dispatch."""
        key = entry_key(kernel, engine, bucket, batch_size)
        self.entries[key] = {"options": dict(options), **meta}
        return key

    def lookup_options(self, kernel: str, engine: str, bucket: tuple,
                       batch_size: Optional[int]) -> Optional[dict]:
        ent = self.entries.get(entry_key(kernel, engine, bucket, batch_size))
        if not isinstance(ent, dict):
            return None
        opts = ent.get("options")
        return dict(opts) if isinstance(opts, dict) else None

    def __len__(self) -> int:
        return len(self.entries)


# ---------------------------------------------------------------------------
# The process-wide active table (what get_plan consults).
# ---------------------------------------------------------------------------
_LOCK = threading.Lock()
_OVERRIDE: Optional[TuningTable] = None       # set_table(TuningTable)
_OVERRIDE_PATH: Optional[str] = None          # set_table("path")
_CACHED: Optional[tuple] = None               # (path, mtime, table|None)


@functools.lru_cache(maxsize=1)
def default_path() -> pathlib.Path:
    """``TUNE_TABLE.json`` at the repo root (three levels above this
    package: src/repro/tune -> repo).  Resolved once: every launch looks
    the table up, and each ``lstat`` of the resolution is a system call
    that can hand the interpreter lock to another thread."""
    return pathlib.Path(__file__).resolve().parents[3] / DEFAULT_TABLE_NAME


def set_table(table=None) -> None:
    """Install the active table programmatically: a :class:`TuningTable`,
    a path string, or ``None`` to restore env/default-file discovery.
    ``REPRO_TUNE_TABLE=off`` still wins — the env kill switch must
    restore hand-picked defaults no matter what code installed."""
    global _OVERRIDE, _OVERRIDE_PATH, _CACHED
    with _LOCK:
        _CACHED = None
        if table is None:
            _OVERRIDE = _OVERRIDE_PATH = None
        elif isinstance(table, TuningTable):
            _OVERRIDE, _OVERRIDE_PATH = table, None
        else:
            _OVERRIDE, _OVERRIDE_PATH = None, str(table)


def _load_cached(path: str) -> Optional[TuningTable]:
    """mtime-validated single-slot cache: dispatch-path lookups must not
    re-read the file per get_plan call."""
    global _CACHED
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    with _LOCK:
        if _CACHED is not None and _CACHED[0] == path \
                and _CACHED[1] == mtime:
            return _CACHED[2]
    try:
        table = TuningTable.load(path)
    except Exception:
        table = None
    with _LOCK:
        _CACHED = (path, mtime, table)
    return table


def active_table() -> Optional[TuningTable]:
    """The table :func:`lookup` consults, or ``None`` (disabled/absent).
    See the module docstring for the resolution order."""
    env = os.environ.get(ENV_VAR)
    if env is not None and env.strip().lower() in _OFF_VALUES:
        return None
    if _OVERRIDE is not None:
        return _OVERRIDE
    if _OVERRIDE_PATH is not None:
        return _load_cached(_OVERRIDE_PATH)
    if env:
        return _load_cached(env)
    return _load_cached(str(default_path()))     # None when absent


def lookup(kernel: str, engine: str, bucket: tuple,
           batch_size: Optional[int]) -> Optional[dict]:
    """Winning options for one point, or ``None`` — the hook
    ``runtime.plan.get_plan`` calls when no explicit option was passed."""
    table = active_table()
    if table is None:
        return None
    return table.lookup_options(kernel, engine, bucket, batch_size)
