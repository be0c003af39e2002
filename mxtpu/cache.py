"""``mxtpu.cache`` — persistent AOT executable cache (ISSUE 13).

At fleet scale compile time *is* availability: every server process
recompiles its full bucket ladder at warmup, and the control plane's
``warm_from=`` handoff only helps while a *live* donor exists.  This
module is the disk layer that survives process death: compiled XLA
executables (``jax.stages.Compiled``) are serialized through
``jax.experimental.serialize_executable`` and stored one-file-per-key
under a cache root, so a rollout, a spot-preempted worker's
replacement, or a scale-from-floor replica warms its ladder with
**zero data-path compiles** — ``ModelRunner._entry`` and the AOT
``TrainStep`` build load-or-compile through :class:`ExecutableCache`
transparently, ``FleetRouter.add_worker`` / the ``Autoscaler`` warm
donor-less replicas from it.

The robustness core is the failure surface, not the happy path:

* **Crash-safe writes** — entry bytes go to a private temp file in the
  cache root, are fsync'd, then ``os.replace``'d onto the final name:
  readers NEVER observe a torn entry, concurrent writers (threads or
  separate processes) race benignly (last atomic rename wins, both
  files are valid for the same key).
* **Verified loads** — every load re-parses the header, checks the
  payload length and sha256 checksum, and revalidates the FULL key
  component dict (model fingerprint, bucket shape, mesh/topology, jax
  version, backend, device kind, contract hash, salt) against what the
  caller expects.  A corrupt, truncated, or stale entry is moved to
  ``<root>/quarantine/`` and the caller recompiles — a wrong
  executable is never returned (the silent-corruption rule PR 7 set
  for canaries applies to the cache too).  The ``pickle.loads`` below
  is the ONE sanctioned raw-deserialize site in the tree (the
  ``raw-deserialize`` mxlint rule confines it here) and it only runs
  AFTER the checksum has passed.  The checksum defends against
  corruption/truncation, not a malicious cache root — point
  ``MXTPU_CACHE_DIR`` at a directory you trust like you trust your
  checkpoints.
* **Degradation, never errors** — a read-only cache dir, a full disk,
  or a jax/backend whose executables do not serialize all fall back to
  plain compile with a ``cache`` flight-recorder event and a
  ``mxtpu_cache_fallback_total`` count; nothing in the serving or
  training path ever raises because the cache is unhealthy.

Failure paths are exercised deterministically through the scripted
cache faults in :mod:`mxtpu.serving.faults` (``CorruptEntry``,
``TruncateEntry``, ``StaleKey``, ``ReadOnlyDir``) consulted at this
module's write seams, plus the :func:`poison_corrupt` /
:func:`poison_truncate` / :func:`poison_stale` helpers tests and the
``--self-check`` CLI use directly.

``python -m mxtpu.cache --self-check`` round-trips a tiny executable
through a throwaway cache root and probes every poisoning path — the
stage ``tools/ci_static.py`` runs.

Knobs (README "Persistent compile cache"): ``MXTPU_CACHE`` (master
switch), ``MXTPU_CACHE_DIR`` (root; unset = no persistence),
``MXTPU_CACHE_SALT`` (extra key component — bump to invalidate).
"""
from __future__ import annotations

import errno
import hashlib
import itertools
import json
import os
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

from . import knobs
from . import obs
from .base import MXNetError

__all__ = ["CacheKey", "ExecutableCache", "default_cache",
           "contract_fingerprint", "poison_corrupt", "poison_truncate",
           "poison_stale", "self_check"]

# On-disk entry layout: magic, a fixed-width decimal header length,
# the JSON header (key components + payload checksum), the payload
# (pickled ``serialize()`` triple).  FORMAT is also a key component so
# a layout change can never alias an old entry.
_MAGIC = b"MXTPUXC1\n"
_FORMAT = 1
_LEN_WIDTH = 10

_QUARANTINE_DIR = "quarantine"

# temp-file uniquifier: pid alone is not enough — two cache INSTANCES
# in one process writing the same key would share a temp name and one
# writer's atomic rename would steal the other's half-written file
_TMP_SEQ = itertools.count()


class _EntryInvalid(Exception):
    """Internal: entry failed verification; ``reason`` is the
    quarantine label (magic|truncated|header|checksum|stale_key)."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(detail or reason)
        self.reason = reason


class CacheKey:
    """An immutable, order-independent component dict plus its sha256
    digest (the entry filename).  Components are all strings; flipping
    ANY component — model fingerprint, bucket shape, mesh/topology,
    jax version, backend, device ids, contract hash, salt — changes
    the digest, and the full dict is ALSO stored in the entry header
    and revalidated on load (a digest collision or a hand-renamed file
    can never smuggle a stale executable in).  ``devices`` are the
    jax devices the keyed program runs on (their ids are a component;
    the objects ride along because a serialized executable names its
    devices by id and has to be loaded onto exactly those)."""

    __slots__ = ("components", "digest", "devices")

    def __init__(self, components: Dict[str, Any], devices=()):
        self.devices = tuple(devices)
        self.components = {str(k): str(v)
                           for k, v in sorted(components.items())}
        blob = json.dumps(self.components, sort_keys=True,
                          separators=(",", ":"))
        self.digest = hashlib.sha256(blob.encode()).hexdigest()

    def filename(self) -> str:
        return f"{self.digest}.mxc"

    def replace(self, **changes: Any) -> "CacheKey":
        """A new key with some components flipped (tests exercise the
        miss-on-any-component contract through this)."""
        comps = dict(self.components)
        comps.update(changes)
        return CacheKey(comps, self.devices)

    def __repr__(self) -> str:
        return f"CacheKey({self.digest[:12]}…, {self.components})"


def contract_fingerprint(root: Optional[Path] = None) -> str:
    """sha256 over the committed ``contracts/`` lockfiles (sorted
    name+content) — the natural cache-validity fingerprint: when the
    pinned program contracts change, every cached executable built
    under the old contracts misses.  Computed once per process."""
    global _CONTRACT_FP
    if root is None:
        if _CONTRACT_FP is not None:
            return _CONTRACT_FP
        root = Path(__file__).resolve().parents[1] / "contracts"
    h = hashlib.sha256()
    if root.is_dir():
        for p in sorted(root.rglob("*.json")):
            h.update(p.relative_to(root).as_posix().encode())
            h.update(b"\0")
            try:
                h.update(p.read_bytes())
            except OSError:
                h.update(b"<unreadable>")
            h.update(b"\0")
    fp = h.hexdigest()[:16]
    if root == Path(__file__).resolve().parents[1] / "contracts":
        _CONTRACT_FP = fp
    return fp


_CONTRACT_FP: Optional[str] = None


class ExecutableCache:
    """One on-disk compiled-executable cache root.

    All methods are thread-safe and never raise on cache trouble: a
    failed ``load`` returns None (after quarantining the bad entry), a
    failed ``store`` returns False (after recording the fallback) —
    the caller compiles either way.  ``faults`` is the deterministic
    fault-injection seam (a :class:`~mxtpu.serving.faults.FaultPlan`
    carrying cache faults, consulted at the write seam and after each
    committed entry); production callers leave it None.
    """

    def __init__(self, root, *, salt: str = "", faults=None):
        self.root = Path(root)
        self.salt = str(salt)
        self._faults = faults
        # leaf lock (acquires nothing inside): counters + the write
        # latch; file operations themselves rely on atomic rename,
        # not on this lock, so cross-PROCESS writers are safe too.
        self._lock = threading.Lock()
        self._stores = 0              # guarded-by: _lock (fault script counter)
        self._write_ok = True         # guarded-by: _lock (latched off on EROFS/EACCES)
        self._stats = {"hit": 0, "miss": 0, "store": 0,       # guarded-by: _lock
                       "fallback": 0, "quarantined": 0}
        self._obs = obs.enabled()
        self._m_quarantined = obs.counter(
            "mxtpu_cache_quarantined_total",
            "Cache entries that failed load verification (corrupt/"
            "truncated/stale) and were moved to quarantine/.",
            labels=("reason",))
        self._m_fallback = obs.counter(
            "mxtpu_cache_fallback_total",
            "Cache degradations that fell back to plain compile "
            "(read-only dir, disk full, unserializable executable).",
            labels=("reason",))
        self._m_store = obs.counter(
            "mxtpu_cache_store_total",
            "Cache entries committed to disk (atomic renames).")
        self.recorder = obs.flight("cache")

    # -- keys -----------------------------------------------------------
    def key(self, *, model: str, shape: Any, mesh: Any = "1dev",
            devices=None, **extra: Any) -> CacheKey:
        """Compose a full cache key: the caller names WHAT was
        compiled (``model`` fingerprint, concrete ``shape``/bucket,
        ``mesh`` topology, anything else via ``extra``) and the
        ``devices`` it runs on (default: the first device, where an
        unplaced ``jax.jit`` runs); the cache adds the environment
        components every entry must match — jax version, backend,
        device kind and ids, contract fingerprint, salt, format."""
        import jax
        devices = tuple(devices) if devices is not None \
            else (jax.devices()[0],)
        comps: Dict[str, Any] = {
            "model": model, "shape": str(shape), "mesh": str(mesh),
            "jax": jax.__version__,
            "backend": devices[0].platform,
            "device": devices[0].device_kind,
            "device_ids": ",".join(str(d.id) for d in devices),
            "contract": contract_fingerprint(),
            "salt": self.salt, "format": str(_FORMAT)}
        for k, v in extra.items():
            comps[k] = str(v)
        return CacheKey(comps, devices)

    def path_for(self, key: CacheKey) -> Path:
        return self.root / key.filename()

    def contains(self, key: CacheKey) -> bool:
        """Cheap existence probe (no verification) — what the fleet
        asks before deciding a replacement can warm from disk."""
        return self.path_for(key).is_file()

    # -- load (verify-or-quarantine) ------------------------------------
    def load(self, key: CacheKey, *, with_meta: bool = False):
        """The checksum-verified loader: returns the loaded executable
        or None (missing / invalid / undeserializable — invalid
        entries are quarantined, never returned).  ``with_meta=True``
        returns ``(executable_or_None, meta)`` instead, where ``meta``
        is the writer's :meth:`store` sidecar dict (``{}`` on a miss)
        — how callers learn e.g. which audit modes the writer process
        ran, knobs being per-process."""
        compiled, meta = self._load(key)
        return (compiled, meta) if with_meta else compiled

    def _load(self, key: CacheKey) -> Tuple[Any, Dict[str, Any]]:
        path = self.path_for(key)
        try:
            blob = path.read_bytes()
        except FileNotFoundError:
            self._bump("miss")
            return None, {}
        except OSError as e:
            self._fallback("read_error", key, err=e)
            return None, {}
        try:
            payload, header = self._verify(blob, key)
        except _EntryInvalid as e:
            self._quarantine(path, e.reason, key, detail=str(e))
            return None, {}
        try:
            import pickle
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            # THE sanctioned raw-deserialize site (raw-deserialize
            # lint rule): the payload checksum was verified above.
            unloaded, in_tree, out_tree = pickle.loads(payload)
            # without execution_devices the executable is loaded
            # across EVERY local device and a one-device program then
            # wants one argument shard per device
            compiled = deserialize_and_load(
                unloaded, in_tree, out_tree,
                execution_devices=list(key.devices) or None)
        except Exception as e:  # jax/backend mismatch survives checksum
            self._quarantine(path, "deserialize", key, detail=repr(e))
            return None, {}
        self._bump("hit")
        if self._obs:
            self.recorder.record("hit", digest=key.digest[:12],
                                 model=key.components.get("model",
                                                          "")[:16])
        meta = header.get("meta")
        return compiled, meta if isinstance(meta, dict) else {}

    def _verify(self, blob: bytes,
                key: CacheKey) -> Tuple[bytes, Dict[str, Any]]:
        """Structural + checksum + key revalidation; returns
        ``(payload bytes, header dict)`` or raises
        :class:`_EntryInvalid`."""
        if not blob.startswith(_MAGIC):
            raise _EntryInvalid("magic", "bad magic")
        off = len(_MAGIC)
        len_line = blob[off:off + _LEN_WIDTH + 1]
        if len(len_line) < _LEN_WIDTH + 1 or \
                not len_line.endswith(b"\n"):
            raise _EntryInvalid("truncated", "short header-length")
        try:
            hlen = int(len_line[:-1])
        except ValueError:
            raise _EntryInvalid("header", "bad header-length") \
                from None
        off += _LEN_WIDTH + 1
        hbytes = blob[off:off + hlen]
        if len(hbytes) < hlen:
            raise _EntryInvalid("truncated", "short header")
        try:
            header = json.loads(hbytes)
        except ValueError:
            raise _EntryInvalid("header", "undecodable header") \
                from None
        payload = blob[off + hlen:]
        want_len = header.get("payload_len")
        if not isinstance(want_len, int) or len(payload) != want_len:
            raise _EntryInvalid(
                "truncated",
                f"payload {len(payload)}B, header says {want_len}")
        digest = hashlib.sha256(payload).hexdigest()
        if digest != header.get("payload_sha256"):
            raise _EntryInvalid("checksum", "payload sha256 mismatch")
        if header.get("key") != key.components:
            raise _EntryInvalid(
                "stale_key",
                f"entry key {header.get('key')} != expected "
                f"{key.components}")
        return payload, header

    # -- store (crash-safe) ---------------------------------------------
    def store(self, key: CacheKey, compiled, *,
              meta: Optional[Dict[str, Any]] = None) -> bool:
        """Serialize + commit one entry crash-safely: temp file in the
        cache root, fsync, atomic ``os.replace``.  Returns False (and
        records the degradation) instead of raising on any trouble.
        ``meta`` is a small JSON-able sidecar stored in the header and
        handed back by ``load(with_meta=True)`` — NOT part of the key
        (an entry written under different meta still hits); callers
        use it for per-process facts like the writer's audit modes."""
        with self._lock:
            if not self._write_ok:
                return False
            k = self._stores
            self._stores += 1
        try:
            import pickle
            from jax.experimental.serialize_executable import serialize
            unloaded, in_tree, out_tree = serialize(compiled)
            payload = pickle.dumps((unloaded, in_tree, out_tree))
        except Exception as e:
            self._fallback("serialize_unsupported", key, err=e)
            return False
        header = json.dumps(
            {"format": _FORMAT, "key": key.components,
             "digest": key.digest,
             "payload_sha256": hashlib.sha256(payload).hexdigest(),
             "payload_len": len(payload),
             "meta": dict(meta or {}),
             "created": time.time(), "writer_pid": os.getpid()},
            sort_keys=True).encode()
        blob = (_MAGIC + f"{len(header):0{_LEN_WIDTH}d}\n".encode()
                + header + payload)
        path = self.path_for(key)
        tmp = self.root / (f".{key.digest}.{os.getpid()}"
                           f".{next(_TMP_SEQ)}.tmp")
        try:
            if self._faults is not None:
                self._faults.before_cache_write(k)
            self.root.mkdir(parents=True, exist_ok=True)
            fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                         0o644)
            try:
                os.write(fd, blob)
                os.fsync(fd)
            finally:
                os.close(fd)
            os.replace(tmp, path)
            self._fsync_dir(self.root)
        except OSError as e:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            if isinstance(e, PermissionError) or \
                    e.errno in (errno.EROFS, errno.EACCES):
                reason = "read_only"
                with self._lock:
                    # latch writes off: a read-only root will not heal
                    # mid-process, and re-failing every compile would
                    # spam the recorder
                    self._write_ok = False
            elif e.errno == errno.ENOSPC:
                reason = "disk_full"
            else:
                reason = "write_error"
            self._fallback(reason, key, err=e)
            return False
        if self._faults is not None:
            self._faults.entry_written(k, path)
        self._bump("store")
        if self._obs:
            self._m_store.inc()
            self.recorder.record("store", digest=key.digest[:12],
                                 bytes=len(blob))
        return True

    def load_or_compile(self, key: CacheKey,
                        compile_fn: Callable[[], Any], *,
                        meta: Optional[Dict[str, Any]] = None
                        ) -> Tuple[Any, str]:
        """``(executable, source)`` where source is ``"disk"`` (a
        verified cache hit) or ``"cold"`` (compiled now; stored for
        the next process if the cache is writable, with ``meta`` as
        the entry's header sidecar)."""
        compiled = self.load(key)
        if compiled is not None:
            return compiled, "disk"
        compiled = compile_fn()
        self.store(key, compiled, meta=meta)
        return compiled, "cold"

    # -- failure bookkeeping --------------------------------------------
    def _bump(self, stat: str, n: int = 1) -> None:
        with self._lock:
            self._stats[stat] += n

    def _quarantine(self, path: Path, reason: str, key: CacheKey,
                    detail: str = "") -> None:
        """Move a failed entry aside (never delete evidence, never
        retry it) and count it.  The quarantined file keeps its digest
        name plus reason + timestamp, so postmortems can inspect what
        the corruption actually was."""
        qdir = self.root / _QUARANTINE_DIR
        dest = qdir / f"{path.name}.{reason}.{os.getpid()}.{int(time.time() * 1e6)}"
        try:
            qdir.mkdir(parents=True, exist_ok=True)
            os.replace(path, dest)
        except OSError:
            try:  # read-only root: at least stop load() retrying it
                os.unlink(path)
            except OSError:
                pass
        self._bump("quarantined")
        if self._obs:
            self._m_quarantined.labels(reason=reason).inc()
            self.recorder.record("quarantine", reason=reason,
                                 digest=key.digest[:12],
                                 detail=detail[:160])

    def _fallback(self, reason: str, key: Optional[CacheKey],
                  err: Optional[BaseException] = None) -> None:
        self._bump("fallback")
        if self._obs:
            self._m_fallback.labels(reason=reason).inc()
            self.recorder.record(
                "fallback", reason=reason,
                digest=key.digest[:12] if key is not None else "",
                error=repr(err)[:160] if err is not None else "")

    @staticmethod
    def _fsync_dir(d: Path) -> None:
        """Make the rename itself durable (crash between rename and
        journal flush must not resurrect the old state as a torn
        view).  Best-effort: not every filesystem allows O_RDONLY
        dir fds."""
        try:
            fd = os.open(d, os.O_RDONLY)
        except OSError:
            return
        try:
            os.fsync(fd)
        except OSError:
            pass
        finally:
            os.close(fd)

    # -- introspection ---------------------------------------------------
    def stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._stats)

    def writable(self) -> bool:
        with self._lock:
            return self._write_ok

    def entries(self) -> int:
        try:
            return sum(1 for _ in self.root.glob("*.mxc"))
        except OSError:
            return 0


# ----------------------------------------------------------------------
# process-wide default (knob-driven)
# ----------------------------------------------------------------------
_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Dict[str, ExecutableCache] = {}  # guarded-by: _DEFAULT_LOCK


def default_cache() -> Optional[ExecutableCache]:
    """The knob-configured process cache: None unless ``MXTPU_CACHE``
    is on AND ``MXTPU_CACHE_DIR`` names a root.  One instance per
    root, shared across every runner/TrainStep in the process (their
    entries can never collide: the key carries the model
    fingerprint)."""
    if not knobs.get("MXTPU_CACHE"):
        return None
    root = str(knobs.get("MXTPU_CACHE_DIR")).strip()
    if not root:
        return None
    salt = str(knobs.get("MXTPU_CACHE_SALT"))
    with _DEFAULT_LOCK:
        cache = _DEFAULT.get(root)
        if cache is None or cache.salt != salt:
            cache = _DEFAULT[root] = ExecutableCache(root, salt=salt)
        return cache


# ----------------------------------------------------------------------
# poisoning helpers — the shared implementation behind the scripted
# cache faults (serving/faults.py) and the self-check probes
# ----------------------------------------------------------------------
def poison_corrupt(path) -> None:
    """Flip one byte inside the payload region (a bit-rot / bad-DMA
    entry: structurally intact, checksum must catch it)."""
    p = Path(path)
    blob = bytearray(p.read_bytes())
    i = len(blob) - max(1, len(blob) // 16)
    blob[i] ^= 0xFF
    p.write_bytes(bytes(blob))


def poison_truncate(path) -> None:
    """Cut the entry in half (a crash mid-write on a filesystem
    without atomic rename semantics, or a partial copy)."""
    p = Path(path)
    blob = p.read_bytes()
    p.write_bytes(blob[:len(blob) // 2])


def poison_stale(path, component: str = "jax",
                 value: str = "0.0.0-stale") -> None:
    """Rewrite one key component in the header, keeping the payload
    checksum VALID — the entry parses and checksums clean but fails
    key revalidation (exactly what an entry from an old jax / old
    contracts looks like after an in-place upgrade)."""
    p = Path(path)
    blob = p.read_bytes()
    off = len(_MAGIC)
    hlen = int(blob[off:off + _LEN_WIDTH])
    off += _LEN_WIDTH + 1
    header = json.loads(blob[off:off + hlen])
    header["key"][component] = value
    hbytes = json.dumps(header, sort_keys=True).encode()
    p.write_bytes(_MAGIC + f"{len(hbytes):0{_LEN_WIDTH}d}\n".encode()
                  + hbytes + blob[off + hlen:])


# ----------------------------------------------------------------------
# self check (the tools/ci_static.py stage)
# ----------------------------------------------------------------------
def self_check(root: Optional[str] = None) -> Dict[str, Any]:
    """Round-trip + poisoning probes on a tiny executable:

    * store → load is a verified hit and the loaded executable
      computes bit-identical results;
    * each poisoning (corrupt byte, truncation, stale key component)
      makes ``load`` return None, quarantines the entry, and a
      re-store recovers;
    * a scripted read-only root degrades ``store`` to False without
      raising (and latches writes off);
    * flipping any key component misses.

    Raises :class:`MXNetError` on any contract violation; returns an
    info dict.  If this jax/backend cannot serialize executables at
    all, that is reported (``serialize_supported: False``) and the
    probes are skipped — that IS the degradation contract, not a
    failure."""
    import shutil
    import tempfile

    import numpy as np
    import jax
    import jax.numpy as jnp

    tmp = root or tempfile.mkdtemp(prefix="mxtpu_cache_check_")
    made_tmp = root is None
    info: Dict[str, Any] = {"root": tmp}
    try:
        cache = ExecutableCache(tmp, salt="self_check")
        x = jnp.arange(8, dtype=jnp.float32)
        compiled = jax.jit(lambda v: v * 2 + 1).lower(x).compile()
        want = np.asarray(compiled(x))  # mxlint: sync-point — probe readback
        key = cache.key(model="self_check", shape="(8,)f32")
        if not cache.store(key, compiled):
            # serialize unsupported here: the fallback path already
            # fired (recorded); nothing further to probe.
            info["serialize_supported"] = False
            return info
        info["serialize_supported"] = True
        loaded = cache.load(key)
        if loaded is None:
            raise MXNetError("cache self_check: round-trip load missed")
        got = np.asarray(loaded(x))  # mxlint: sync-point — probe readback
        if not np.array_equal(want, got):
            raise MXNetError(
                f"cache self_check: loaded executable disagrees "
                f"({got} != {want})")

        # any flipped key component must miss
        for comp, val in (("model", "other"), ("shape", "(9,)f32"),
                          ("mesh", "2dev"), ("jax", "0.0.0"),
                          ("contract", "feedfeedfeedfeed")):
            if cache.load(key.replace(**{comp: val})) is not None:
                raise MXNetError(
                    f"cache self_check: flipped key component "
                    f"{comp!r} still hit")

        # poisoning probes: each must load None + quarantine, and a
        # fresh store must recover
        path = cache.path_for(key)
        probes = (("corrupt", poison_corrupt),
                  ("truncate", poison_truncate),
                  ("stale", poison_stale))
        for name, poison in probes:
            if not cache.contains(key):
                cache.store(key, compiled)
            poison(path)
            if cache.load(key) is not None:
                raise MXNetError(
                    f"cache self_check: poisoned entry ({name}) "
                    f"was served")
            if cache.contains(key):
                raise MXNetError(
                    f"cache self_check: poisoned entry ({name}) "
                    f"not quarantined")
        st = cache.stats()
        if st["quarantined"] != len(probes):
            raise MXNetError(
                f"cache self_check: expected {len(probes)} "
                f"quarantines, saw {st['quarantined']}")
        qdir = Path(tmp) / _QUARANTINE_DIR
        if sum(1 for _ in qdir.iterdir()) != len(probes):
            raise MXNetError(
                "cache self_check: quarantine dir does not hold the "
                "poisoned entries")

        # read-only degradation: scripted PermissionError at the
        # write seam (chmod is unreliable here — CI roots run as
        # uid 0, which ignores mode bits)
        class _Deny:
            def before_cache_write(self, k):
                raise PermissionError("self_check: read-only root")

            def entry_written(self, k, path):
                pass

        ro = ExecutableCache(Path(tmp) / "ro", salt="self_check",
                             faults=_Deny())
        if ro.store(key, compiled):
            raise MXNetError(
                "cache self_check: store on a read-only root "
                "claimed success")
        if ro.writable():
            raise MXNetError(
                "cache self_check: read-only root did not latch "
                "writes off")
        info.update(stats=st, round_trip=True, poisons=len(probes),
                    read_only_fallback=True)
        return info
    finally:
        if made_tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def _main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python -m mxtpu.cache")
    ap.add_argument("--self-check", action="store_true",
                    help="round-trip + poisoning probes on a tiny "
                         "executable (default action)")
    ap.add_argument("--root", default=None,
                    help="probe inside this directory instead of a "
                         "throwaway tempdir")
    args = ap.parse_args(argv)
    info = self_check(root=args.root)
    print(f"cache.self_check OK: {info}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(_main())
