"""Persistent cross-process plan cache: tier 2 of `Embedder.plan`.

Tier 1 (in `Embedder`) matches plans by array identity — O(1), but it
dies with the process.  GEE's practical workload re-embeds the *same
graph* many times as labels churn, across restarts, CI reruns, and
serving replicas; for those, graph identity is content, not arrays.
This module stores each plan's **host half** (w_eff, Pallas destination
packing — everything expensive and device-free) on disk, keyed on:

    (graph fingerprint, backend name, backend plan_version,
     config fields)

so a fresh process skips host packing entirely and goes straight to
`Backend.plan_finalize` (cheap device placement).

Location: ``$REPRO_PLAN_CACHE`` if set (the values ``0 / off / none /
disable(d)`` or empty disable the tier), else
``$XDG_CACHE_HOME/repro-gee/plans`` (``~/.cache/repro-gee/plans``).

Robustness contract (tested):
  * writes are atomic (tmp file + os.replace) — a crashed writer can
    never leave a partial entry visible;
  * entries are versioned (format + per-backend plan_version) and
    self-describing — a stale entry is treated as a miss and rebuilt;
  * a corrupt entry (truncated, garbage) is deleted and rebuilt — the
    cache can only ever cost a rebuild, never a wrong answer;
  * a hit is verified against the request's full metadata, so a key
    collision degrades to a miss.

Plan directories would otherwise grow without bound (every distinct
(graph, backend, config) writes an entry, and serving fleets churn
graphs): construct with ``max_entries=`` / ``max_bytes=`` (or set
``REPRO_PLAN_CACHE_MAX_ENTRIES`` / ``REPRO_PLAN_CACHE_MAX_BYTES`` for
the default cache) and the cache evicts **least-recently-used**
entries after each store — a hit touches the entry's mtime, so
`last_used` recency is tracked by the filesystem with no side index to
corrupt.  Eviction is best-effort like everything else here: it can
only ever cost a rebuild.

``PlanDiskCache.clear()`` wipes the directory (also: just delete it),
and ``python -m repro.encoder.plan_cache --stats|--clear`` does both
from the shell.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import time
from dataclasses import asdict
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

from repro import obs

FORMAT_VERSION = 1
_META_KEY = "__meta__"
_OFF_VALUES = ("", "0", "off", "none", "disable", "disabled")


def config_token(config) -> str:
    """Canonical string of the config fields a plan depends on.  The
    `backend` field is excluded: the resolved backend NAME is its own
    key component (so `backend="auto"` and an explicit name that auto
    resolves to share entries).  `row_partition` is deliberately IN
    the token — a partitioned plan's host artifacts are bucketed and
    remapped for one specific (lo, hi) slice, so a resharded
    deployment addresses different entries and can never hit a stale
    plan (tested in test_encoder.py::TestOwnedRows)."""
    d = {k: v for k, v in asdict(config).items() if k != "backend"}
    return json.dumps(d, sort_keys=True)


class PlanDiskCache:
    """Content-addressed npz store for plan host halves.

    `max_entries` / `max_bytes` (None = unbounded) cap the directory;
    when a store pushes it over, least-recently-used entries are
    evicted (`last_used` = file mtime, refreshed on every hit)."""

    def __init__(self, root, *, max_entries: Optional[int] = None,
                 max_bytes: Optional[int] = None):
        self.root = Path(root)
        self.max_entries = max_entries
        self.max_bytes = max_bytes

    # -- keying -----------------------------------------------------------

    def describe(self, fingerprint: str, backend, config
                 ) -> Dict[str, Any]:
        """The full metadata a cached entry must match to be served."""
        return {"format": FORMAT_VERSION,
                "fingerprint": fingerprint,
                "backend": backend.name,
                "plan_version": backend.plan_version,
                "config": config_token(config)}

    @staticmethod
    def key(meta: Dict[str, Any]) -> str:
        blob = json.dumps(meta, sort_keys=True).encode()
        return hashlib.blake2b(blob, digest_size=16).hexdigest()

    def path(self, meta: Dict[str, Any]) -> Path:
        return self.root / (self.key(meta) + ".npz")

    # -- load / store -----------------------------------------------------

    def load(self, meta: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """The stored host dict, or None (miss / stale / corrupt).

        Corrupt entries are deleted so the subsequent rebuild's store
        replaces them; stale ones (old format, different config hash
        behind a colliding key) are simply ignored."""
        path = self.path(meta)
        if not path.exists():
            return None
        try:
            with np.load(path, allow_pickle=False) as d:
                stored = json.loads(str(d[_META_KEY][()]))
                if stored != meta:
                    obs.counter("repro_encoder_plan_cache_total",
                                event="stale")
                    return None                       # stale / collision
                host = {k: d[k] for k in d.files if k != _META_KEY}
            try:
                os.utime(path)          # refresh last_used for the LRU
            except OSError:
                pass
            return host
        except Exception:
            obs.counter("repro_encoder_plan_cache_total",
                        event="corrupt")
            try:
                path.unlink()
            except OSError:
                pass
            return None

    def store(self, meta: Dict[str, Any], host: Dict[str, Any]) -> bool:
        """Atomically persist `host` under `meta`'s key.  Best-effort:
        an unwritable cache dir must never break embedding."""
        path = self.path(meta)
        tmp = path.with_name(f"{path.stem}.tmp{os.getpid()}.npz")
        try:
            self.root.mkdir(parents=True, exist_ok=True)
            with open(tmp, "wb") as f:
                np.savez(f, **{_META_KEY: np.asarray(json.dumps(meta))},
                         **host)
            os.replace(tmp, path)
            self.evict()
            return True
        except Exception:
            try:
                tmp.unlink()
            except OSError:
                pass
            return False

    # -- maintenance ------------------------------------------------------

    def entries(self) -> list[Path]:
        if not self.root.is_dir():
            return []
        return sorted(p for p in self.root.glob("*.npz"))

    def evict(self) -> int:
        """Drop least-recently-used entries until the directory fits
        `max_entries` / `max_bytes`.  Returns how many were removed.
        Best-effort: races with other processes (an entry vanishing
        under us) and unwritable dirs are ignored."""
        if self.max_entries is None and self.max_bytes is None:
            return 0
        stats = []
        for p in self.entries():
            try:
                st = p.stat()
                stats.append((st.st_mtime, p.name, st.st_size, p))
            except OSError:
                continue
        stats.sort()                    # oldest last_used first
        total = sum(s[2] for s in stats)
        removed = 0
        while stats and (
                (self.max_entries is not None
                 and len(stats) > self.max_entries)
                or (self.max_bytes is not None
                    and total > self.max_bytes)):
            _, _, size, path = stats.pop(0)
            try:
                path.unlink()
                removed += 1
            except OSError:
                pass
            total -= size
        if removed:
            obs.counter("repro_encoder_plan_cache_total", removed,
                        event="evict")
        return removed

    def stats(self) -> Dict[str, Any]:
        """Directory summary for the CLI / observability."""
        entries = []
        for p in self.entries():
            try:
                st = p.stat()
                entries.append((st.st_mtime, st.st_size))
            except OSError:
                continue
        now = time.time()
        return {"root": str(self.root),
                "entries": len(entries),
                "bytes": sum(s for _, s in entries),
                "max_entries": self.max_entries,
                "max_bytes": self.max_bytes,
                "oldest_idle_s": (now - min(m for m, _ in entries)
                                  if entries else 0.0),
                "newest_idle_s": (now - max(m for m, _ in entries)
                                  if entries else 0.0)}

    def clear(self) -> int:
        """Delete every entry; returns how many were removed."""
        removed = 0
        for p in self.entries():
            try:
                p.unlink()
                removed += 1
            except OSError:
                pass
        return removed


def _env_limit(name: str) -> Optional[int]:
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return None
    try:
        val = int(raw)
    except ValueError:
        return None
    return val if val > 0 else None


def default_cache() -> Optional[PlanDiskCache]:
    """Resolve the process-wide default cache from the environment
    (None = persistent tier disabled).  REPRO_PLAN_CACHE_MAX_ENTRIES /
    REPRO_PLAN_CACHE_MAX_BYTES bound it with LRU eviction."""
    limits = {"max_entries": _env_limit("REPRO_PLAN_CACHE_MAX_ENTRIES"),
              "max_bytes": _env_limit("REPRO_PLAN_CACHE_MAX_BYTES")}
    env = os.environ.get("REPRO_PLAN_CACHE")
    if env is not None:
        if env.strip().lower() in _OFF_VALUES:
            return None
        return PlanDiskCache(env, **limits)
    base = os.environ.get("XDG_CACHE_HOME") or (Path.home() / ".cache")
    return PlanDiskCache(Path(base) / "repro-gee" / "plans", **limits)


def main(argv=None) -> int:
    """CLI: inspect or clear the persistent plan cache.

        python -m repro.encoder.plan_cache --stats
        python -m repro.encoder.plan_cache --clear
        python -m repro.encoder.plan_cache --dir /path --stats
    """
    ap = argparse.ArgumentParser(
        prog="repro.encoder.plan_cache",
        description="Inspect or clear the persistent GEE plan cache.")
    ap.add_argument("--dir", default=None,
                    help="cache directory (default: the resolved "
                         "REPRO_PLAN_CACHE / XDG location)")
    ap.add_argument("--stats", action="store_true",
                    help="print entry count / bytes / idle ages "
                         "(the default action)")
    ap.add_argument("--clear", action="store_true",
                    help="delete every cached entry")
    args = ap.parse_args(argv)
    cache = (PlanDiskCache(args.dir) if args.dir is not None
             else default_cache())
    if cache is None:
        print("plan cache disabled (REPRO_PLAN_CACHE="
              f"{os.environ.get('REPRO_PLAN_CACHE')!r})")
        return 1
    if args.clear:
        print(f"cleared {cache.clear()} entr(y|ies) from {cache.root}")
    if args.stats or not args.clear:
        st = cache.stats()
        print(f"root:        {st['root']}")
        print(f"entries:     {st['entries']}")
        print(f"bytes:       {st['bytes']:,}")
        print(f"limits:      max_entries={st['max_entries']} "
              f"max_bytes={st['max_bytes']}")
        if st["entries"]:
            print(f"oldest idle: {st['oldest_idle_s']:.0f}s   "
                  f"newest idle: {st['newest_idle_s']:.0f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
