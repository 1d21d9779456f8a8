"""Persisted commute-embedding artifacts: the query-scale read path's store.

The exact pipeline (chain build + solve) is the *write* path; queries should
never pay it again.  :class:`EmbeddingStore` persists each transition's
committed embedding -- the (n, k_RP) sketch ``Z`` plus the graph volume, the
degree vector and the column-mean ``zbar`` -- as a compact row-panel artifact
that readers (:mod:`repro.core.query`) stream without ever touching live
solver state.  ``SequenceDetector.push`` publishes here after each solve, so
an artifact is by construction a *committed* snapshot of the sketch: a crash
mid-publish leaves the previous embedding current, never a torn one.

The store reuses the :class:`~repro.store.tilestore.TileStore` durability
idioms exactly:

* an artifact's ``Z`` is one file of row panels, written to a temp file
  and ``os.replace``d into place (atomic on POSIX); ``aux`` (vol / deg /
  zbar) likewise.  One file, not one per panel: a publish's file-system
  calls do not grow with the panel count, and readers still map one panel
  at a time;
* an embedding id joins the manifest only once its ``Z`` file and the aux
  sidecar exist (commit-on-complete; re-opening after a crash sees only
  complete embeddings);
* the manifest is fingerprinted on (seed, k, codec, geometry) plus a
  caller-supplied ``meta`` dict -- re-creating a store under different
  parameters is rejected loudly instead of silently serving a stale sketch
  (a ``Z`` drawn under another seed is a *different random projection*; its
  distances are meaningless against this run's queries);
* panels are stored through the tile codecs: ``raw`` (fp32 .npy) or ``bf16``
  (uint16 bit patterns, half the bytes, decoded on-device by the query
  kernel).  ``zstd`` has no device-decodable stored form and is rejected --
  the query path is built around encoded panel shipping.

:class:`EmbeddingHandle` satisfies the snapshot-handle panel protocol
(``shape`` / ``dtype`` / ``panel_rows`` / ``read_panel`` /
``read_panel_info`` / ``read_panel_encoded_info``), so the generic
:class:`~repro.store.pipeline.PanelPipeline` streams ``Z`` row panels with
the same prefetch/accounting machinery the chain executors use.

A store object also keeps one artifact on the device after its first query
(:class:`ResidentArtifact`): the query's pass over the stored panels retains
them there, and later queries of that id on that device, through the same
store object, read them from device memory instead of streaming ``Z`` again.
Only an artifact whose retained bytes fit :func:`resident_budget` is kept; a
larger one streams on every query.  The copy is keyed by ``(emb_id,
device)`` and is the one served last: a miss on another key replaces it,
every ``put_embedding`` through the object drops it, and so does
``remove_embedding`` of its id.  Invalidation is per store object: a put
through another object of the same directory does not reach this one's copy.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterator

import numpy as np

from repro.store.tilestore import MANIFEST_NAME, resolve_codec

# v2: one Z file per artifact (v1 kept one file per panel).
_FORMAT_VERSION = 2
_AUX_NAME = "aux.npz"

# Codecs with a device-decodable stored form only: the query kernel ships
# panels encoded (uint16 bf16 bits widen in VMEM), which zstd cannot do.
EMB_CODECS = ("raw", "bf16")

# An artifact stays on the device after its first query while its retained
# panels and degree slices take at most this share of the device's free memory.
RESIDENT_SHARE = 1 / 8

# The free memory counted on a device that reports no memory stats (the CPU
# backend): none, since the store cannot see what fits there, so every query
# streams, as one of an artifact over the budget does on a chip.
NO_STATS_FREE_BYTES = 0


def resident_budget(device) -> int:
    """Bytes one resident artifact may take on ``device``: ``RESIDENT_SHARE``
    of its free memory, ``bytes_limit - bytes_in_use`` of its
    ``memory_stats()``, or of ``NO_STATS_FREE_BYTES`` where it reports
    none."""
    stats = device.memory_stats() or {}
    if "bytes_limit" in stats:
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    else:
        free = NO_STATS_FREE_BYTES
    return int(RESIDENT_SHARE * max(free, 0))


@dataclass
class EmbManifest:
    """Static geometry + provenance fingerprint of every embedding artifact.

    ``seed`` is part of the fingerprint alongside (k, codec, geometry): two
    stores with equal shapes but different projection seeds hold incomparable
    sketches, and resuming one as the other must fail loudly.  ``meta`` is
    the caller's content label (dataset, generator params), with the same
    reject-on-mismatch contract as the snapshot store.
    """

    n: int
    k: int
    panel_rows: int
    dtype: str
    codec: str = "raw"
    seed: int = 0
    embeddings: list[str] = field(default_factory=list)
    meta: dict = field(default_factory=dict)
    version: int = _FORMAT_VERSION

    def __post_init__(self):
        if self.n < 1 or self.k < 1:
            raise ValueError(f"need n >= 1 and k >= 1, got n={self.n} k={self.k}")
        if self.panel_rows < 1 or self.n % self.panel_rows:
            raise ValueError(
                f"panel_rows {self.panel_rows} must divide n={self.n}"
            )

    @property
    def panels(self) -> int:
        return self.n // self.panel_rows

    def fingerprint(self) -> tuple:
        return (self.n, self.k, self.panel_rows, self.dtype, self.codec, self.seed)

    def to_json(self) -> str:
        return json.dumps(
            {
                "version": self.version,
                "kind": "embstore",
                "n": self.n,
                "k": self.k,
                "panel_rows": self.panel_rows,
                "dtype": self.dtype,
                "codec": self.codec,
                "seed": self.seed,
                "embeddings": list(self.embeddings),
                "meta": dict(self.meta),
            },
            indent=2,
        )

    @classmethod
    def from_json(cls, text: str) -> "EmbManifest":
        d = json.loads(text)
        if d.get("kind") != "embstore":
            raise ValueError(
                f"manifest kind {d.get('kind')!r} is not an embedding store "
                "(a TileStore directory cannot be opened as an EmbeddingStore)"
            )
        version = d.get("version", 0)
        if version > _FORMAT_VERSION:
            raise ValueError(f"embstore format v{version} is newer than this reader")
        if version < _FORMAT_VERSION:
            raise ValueError(
                f"embstore format v{version} keeps a file per Z panel; this reader "
                f"reads v{_FORMAT_VERSION} (one Z file per artifact): publish into "
                "a fresh directory"
            )
        return cls(
            n=int(d["n"]),
            k=int(d["k"]),
            panel_rows=int(d["panel_rows"]),
            dtype=str(d["dtype"]),
            codec=str(d.get("codec", "raw")),
            seed=int(d.get("seed", 0)),
            embeddings=[str(s) for s in d.get("embeddings", [])],
            meta=dict(d.get("meta", {})),
            version=int(d.get("version", _FORMAT_VERSION)),
        )


@dataclass
class ResidentArtifact:
    """One committed artifact kept on a device after its first query.

    The query's streaming pass appends to ``panels``, in row order, each
    panel's origin, its stored-form Z rows and its (1, panel_rows) 1/deg
    slice, as the query kernel takes them; ``inv_deg`` is the host 1/deg
    vector the queries' own terms read.  ``epoch`` is the store's count of
    puts and removes when the fill began.
    """

    emb_id: str
    device: object
    inv_deg: np.ndarray
    epoch: int
    panels: list = field(default_factory=list)  # [(row0, z, 1/deg)], device arrays

    @property
    def nbytes(self) -> int:
        return sum(z.nbytes + d.nbytes for _, z, d in self.panels)


def default_panel_rows(n: int, want: int = 256) -> int:
    """The largest divisor of ``n`` <= ``want`` (MXU-alignment preferred)."""
    from repro.kernels.tiling import fit

    return fit(n, want)


class EmbeddingStore:
    """A sequence of committed (Z, vol, deg, zbar) embedding artifacts.

    Use :meth:`create` / :meth:`open` rather than the constructor::

        store = EmbeddingStore.create(dir_or_none, n=1024, k=14, seed=0)
        store.put_embedding("t0003", z, vol, deg)     # publish one artifact
        h = store.latest()                            # EmbeddingHandle
        for row0 in range(0, h.shape[0], h.panel_rows):
            panel = h.read_panel(row0, h.panel_rows)

    ``root=None`` selects the host-RAM backend (same API, dict of arrays).

    The store object holds at most one :class:`ResidentArtifact`, the one
    served last (:meth:`resident`, :meth:`resident_fill`,
    :meth:`finish_fill`).  Every ``put_embedding`` drops it, as does
    ``remove_embedding`` of its id, and a fill that was streaming while
    either ran is not kept.
    """

    def __init__(self, manifest: EmbManifest, root: str | Path | None):
        if manifest.codec not in EMB_CODECS:
            raise ValueError(
                f"embedding store codec must be one of {EMB_CODECS}, got "
                f"{manifest.codec!r} (the query kernel needs a device-"
                "decodable stored form)"
            )
        self.manifest = manifest
        self.root = Path(root) if root is not None else None
        self._ram_z: dict[str, np.ndarray] = {}
        self._ram_aux: dict[str, dict[str, np.ndarray]] = {}
        self._resident: ResidentArtifact | None = None
        self._resident_epoch = 0  # bumped by every put or remove
        self._resident_lock = threading.Lock()
        self.codec = resolve_codec(manifest.codec, fallback=False)
        if self.codec.name == "bf16" and np.dtype(manifest.dtype) != np.float32:
            raise ValueError(
                f"bf16 codec stores float32 embeddings only, not {manifest.dtype}"
            )

    # -- lifecycle -----------------------------------------------------------

    @classmethod
    def create(
        cls,
        root: str | Path | None,
        *,
        n: int,
        k: int,
        panel_rows: int | None = None,
        dtype="float32",
        codec: str = "raw",
        seed: int = 0,
        meta: dict | None = None,
    ) -> "EmbeddingStore":
        """New store at ``root`` (made if missing); ``root=None`` = RAM-backed.

        Resuming an existing directory requires a matching fingerprint
        (seed, k, codec, geometry) AND matching meta -- committed artifacts
        from a differently-parameterized run are rejected, never served.
        """
        pr = default_panel_rows(n) if panel_rows is None else int(panel_rows)
        manifest = EmbManifest(
            n=n, k=k, panel_rows=pr, dtype=np.dtype(dtype).name,
            codec=resolve_codec(codec).name, seed=int(seed), meta=dict(meta or {}),
        )
        store = cls(manifest, root)
        if store.root is not None:
            store.root.mkdir(parents=True, exist_ok=True)
            existing = store.root / MANIFEST_NAME
            if existing.exists():
                old = EmbManifest.from_json(existing.read_text())
                if old.fingerprint() != manifest.fingerprint():
                    raise ValueError(
                        f"embedding store at {root} already exists with an "
                        f"incompatible fingerprint {old.fingerprint()} != "
                        f"requested {manifest.fingerprint()} "
                        "(n, k, panel_rows, dtype, codec, seed); use a fresh "
                        "directory -- a differently-seeded sketch is a "
                        "different random projection"
                    )
                if meta is not None and old.meta != manifest.meta:
                    if old.meta or old.embeddings:
                        raise ValueError(
                            f"embedding store at {root} holds different content: "
                            f"meta {old.meta or '<unlabeled, has embeddings>'} != "
                            f"requested {manifest.meta}; use a fresh directory"
                        )
                store.manifest = old  # resume: keep committed embeddings
                if meta is not None and old.meta != manifest.meta:
                    store.manifest.meta = manifest.meta
                    store._write_manifest()
            else:
                store._write_manifest()
        return store

    @classmethod
    def open(cls, root: str | Path) -> "EmbeddingStore":
        root = Path(root)
        manifest = EmbManifest.from_json((root / MANIFEST_NAME).read_text())
        return cls(manifest, root)

    def _write_manifest(self) -> None:
        if self.root is None:
            return
        tmp = self.root / (MANIFEST_NAME + ".tmp")
        tmp.write_text(self.manifest.to_json())
        os.replace(tmp, self.root / MANIFEST_NAME)

    def _refresh_manifest(self) -> None:
        """Read-modify-write guard: re-read the committed list before mutating
        (several instances may share one directory over a run's lifetime)."""
        if self.root is None:
            return
        path = self.root / MANIFEST_NAME
        if path.exists():
            self.manifest.embeddings = EmbManifest.from_json(
                path.read_text()
            ).embeddings

    # -- geometry ------------------------------------------------------------

    @property
    def n(self) -> int:
        return self.manifest.n

    @property
    def k(self) -> int:
        return self.manifest.k

    @property
    def panel_rows(self) -> int:
        return self.manifest.panel_rows

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self.manifest.dtype)

    @property
    def embedding_ids(self) -> list[str]:
        return list(self.manifest.embeddings)

    def __len__(self) -> int:
        return len(self.manifest.embeddings)

    # -- panel I/O -----------------------------------------------------------

    def _z_path(self, emb_id: str) -> Path:
        assert self.root is not None
        return self.root / emb_id / f"z{self.codec.suffix}"

    def _aux_path(self, emb_id: str) -> Path:
        assert self.root is not None
        return self.root / emb_id / _AUX_NAME

    def has_z(self, emb_id: str) -> bool:
        if self.root is None:
            return emb_id in self._ram_z
        return self._z_path(emb_id).exists()

    def has_aux(self, emb_id: str) -> bool:
        if self.root is None:
            return emb_id in self._ram_aux
        return self._aux_path(emb_id).exists()

    def _load_stored(self, emb_id: str, p: int) -> np.ndarray:
        if self.root is None:
            z = self._ram_z[emb_id]
        else:
            z = np.load(self._z_path(emb_id), mmap_mode="r")
        pr = self.panel_rows
        return z[p * pr : (p + 1) * pr]

    def read_panel_stored(self, emb_id: str, p: int) -> np.ndarray:
        """One (panel_rows, k) panel in its *stored* form (raw fp32 or uint16
        bf16 bit patterns -- what the query kernel decodes on-device)."""
        if not (0 <= p < self.manifest.panels):
            raise IndexError(f"panel {p} outside {self.manifest.panels} panels")
        arr = np.asarray(self._load_stored(emb_id, p))
        want = (self.panel_rows, self.k)
        if arr.shape != want:
            raise ValueError(
                f"panel {p} of {emb_id!r} stored as {arr.shape}, manifest says {want}"
            )
        return arr

    def read_panel(self, emb_id: str, p: int) -> np.ndarray:
        """One (panel_rows, k) dense *decoded* panel."""
        stored = self.read_panel_stored(emb_id, p)
        arr = self.codec.decode(stored, self.panel_rows, self.dtype)
        return np.asarray(arr).reshape(self.panel_rows, self.k)

    def panel_nbytes_stored(self, emb_id: str, p: int) -> int:
        """Bytes one stored panel occupies (its slice of the Z file)."""
        itemsize = 2 if self.codec.name == "bf16" else self.dtype.itemsize
        return self.panel_rows * self.k * itemsize

    def read_aux(self, emb_id: str) -> dict[str, np.ndarray]:
        """``{vol: (), deg: (n,), zbar: (k,)}`` -- the small fp32/fp64 sidecar."""
        if self.root is None:
            aux = self._ram_aux[emb_id]
        else:
            with np.load(self._aux_path(emb_id)) as z:
                aux = {name: np.asarray(z[name]) for name in z.files}
        for name in ("vol", "deg", "zbar"):
            if name not in aux:
                raise ValueError(f"aux sidecar of {emb_id!r} is missing {name!r}")
        return aux

    # -- write path ----------------------------------------------------------

    def put_embedding(
        self, emb_id: str, z, vol, deg, *, zbar=None
    ) -> "EmbeddingHandle":
        """Persist one committed embedding artifact and commit it.

        ``z`` is the (n, k) sketch (host array or jax array -- copied to host
        here, so the reader never aliases live solver buffers), ``vol`` the
        scalar graph volume, ``deg`` the (n,) degree vector.  ``zbar`` (the
        column mean of Z, which the centroid-anomaly query needs) defaults to
        being computed here.  A Z file already on disk is kept (resume after
        a publish torn before its aux); the id joins the manifest only once
        the Z file and the aux sidecar exist.
        """
        if "/" in emb_id or emb_id in ("", ".", ".."):
            raise ValueError(f"bad embedding id {emb_id!r}")
        z = np.ascontiguousarray(np.asarray(z, dtype=self.dtype))
        if z.shape != (self.n, self.k):
            raise ValueError(
                f"embedding is {z.shape}, store holds ({self.n}, {self.k})"
            )
        deg = np.asarray(deg, dtype=np.float32).reshape(-1)
        if deg.shape != (self.n,):
            raise ValueError(f"deg is {deg.shape}, want ({self.n},)")
        zbar = (
            z.mean(axis=0, dtype=np.float64).astype(np.float32)
            if zbar is None
            else np.asarray(zbar, dtype=np.float32).reshape(self.k)
        )
        aux = {
            "vol": np.asarray(float(vol), dtype=np.float64),
            "deg": deg,
            "zbar": zbar,
        }
        try:
            if not self.has_z(emb_id):  # else resume after a partial publish
                self._store_z(emb_id, np.asarray(self.codec.encode(z)))
            self._store_aux(emb_id, aux)
            self._commit(emb_id)
        finally:
            self._drop_resident()  # latest() now serves another artifact
        return self.embedding(emb_id)

    def _store_z(self, emb_id: str, stored: np.ndarray) -> None:
        if self.root is None:
            self._ram_z[emb_id] = np.array(stored, copy=True)
            return
        path = self._z_path(emb_id)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".tmp")
        with open(tmp, "wb") as f:
            np.save(f, stored)
        os.replace(tmp, path)  # atomic: old or new, never torn

    def _store_aux(self, emb_id: str, aux: dict[str, np.ndarray]) -> None:
        if self.root is None:
            self._ram_aux[emb_id] = {k: np.array(v, copy=True) for k, v in aux.items()}
            return
        path = self._aux_path(emb_id)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".tmp")
        with open(tmp, "wb") as f:
            np.savez(f, **aux)
        os.replace(tmp, path)

    def _commit(self, emb_id: str) -> None:
        has_z, has_aux = self.has_z(emb_id), self.has_aux(emb_id)
        if not (has_z and has_aux):
            raise ValueError(
                f"embedding {emb_id!r} incomplete: "
                f"z={'ok' if has_z else 'missing'}, aux={'ok' if has_aux else 'missing'}"
            )
        self._refresh_manifest()
        if emb_id not in self.manifest.embeddings:
            self.manifest.embeddings.append(emb_id)
            self._write_manifest()

    def remove_embedding(self, emb_id: str) -> None:
        """Drop an artifact (manifest entry first, then panels -- a crash in
        between leaves orphan panels, never a committed id without panels)."""
        if "/" in emb_id or emb_id in ("", ".", ".."):
            raise ValueError(f"bad embedding id {emb_id!r}")
        self._refresh_manifest()
        if emb_id in self.manifest.embeddings:
            self.manifest.embeddings.remove(emb_id)
            self._write_manifest()
        self._drop_resident(emb_id)
        if self.root is None:
            self._ram_z.pop(emb_id, None)
            self._ram_aux.pop(emb_id, None)
        else:
            emb_dir = self.root / emb_id
            if emb_dir.exists():
                shutil.rmtree(emb_dir)

    # -- device-resident copy -------------------------------------------------

    def resident_nbytes(self) -> int:
        """Bytes one artifact keeps on the device: every panel in its stored
        form plus its float32 (1, panel_rows) 1/deg slice.  A lower bound on
        the memory they take: the runtime's tiled layout may pad them."""
        stored = 2 if self.codec.name == "bf16" else self.dtype.itemsize
        return self.manifest.panels * self.panel_rows * (self.k * stored + 4)

    def resident(self, emb_id: str, device) -> ResidentArtifact | None:
        """The copy of ``emb_id`` kept on ``device``, if that is the one kept."""
        r = self._resident
        if r is not None and r.emb_id == emb_id and r.device == device:
            return r
        return None

    def resident_fill(
        self, emb_id: str, device, inv_deg: np.ndarray
    ) -> ResidentArtifact | None:
        """Start a miss of ``emb_id`` on ``device``: drop the kept copy (the
        store keeps one) and return an empty :class:`ResidentArtifact` for
        the streaming pass to append its panels to, or ``None`` when the
        artifact does not fit :func:`resident_budget`."""
        with self._resident_lock:
            self._resident = None
            epoch = self._resident_epoch
        if self.resident_nbytes() > resident_budget(device):
            return None
        return ResidentArtifact(emb_id, device, inv_deg, epoch)

    def finish_fill(self, fill: ResidentArtifact) -> bool:
        """Keep ``fill`` unless a put or remove ran since it began."""
        with self._resident_lock:
            if fill.epoch != self._resident_epoch:
                return False
            self._resident = fill
            return True

    def _drop_resident(self, emb_id: str | None = None) -> None:
        """Drop the kept copy (only if it holds ``emb_id``, when given)."""
        with self._resident_lock:
            self._resident_epoch += 1
            r = self._resident
            if r is not None and emb_id in (None, r.emb_id):
                self._resident = None

    # -- read path -----------------------------------------------------------

    def embedding(self, emb_id: str) -> "EmbeddingHandle":
        if emb_id not in self.manifest.embeddings:
            raise KeyError(
                f"embedding {emb_id!r} not committed; have {self.manifest.embeddings}"
            )
        return EmbeddingHandle(self, emb_id)

    def latest(self) -> "EmbeddingHandle":
        """The most recently committed artifact (what "now" queries serve)."""
        if not self.manifest.embeddings:
            raise KeyError("embedding store is empty: nothing committed yet")
        return EmbeddingHandle(self, self.manifest.embeddings[-1])

    def iter_embeddings(self) -> Iterator["EmbeddingHandle"]:
        for eid in self.manifest.embeddings:
            yield EmbeddingHandle(self, eid)


@dataclass(frozen=True)
class EmbeddingHandle:
    """Store-backed stand-in for a resident (n, k) embedding ``Z``.

    Satisfies the panel-streaming protocol (``shape`` / ``dtype`` /
    ``panel_rows`` / ``read_panel`` / ``read_panel_info`` /
    ``read_panel_encoded_info``), so :class:`~repro.store.PanelPipeline`
    streams it exactly like a snapshot handle.  ``vol`` / ``deg`` / ``zbar``
    expose the aux sidecar (cached after the first read -- it is a few n
    floats, not an n^2 object).
    """

    store: EmbeddingStore
    emb_id: str

    @property
    def shape(self) -> tuple[int, int]:
        return (self.store.n, self.store.k)

    @property
    def dtype(self) -> np.dtype:
        return self.store.dtype

    @property
    def nbytes(self) -> int:
        return self.store.n * self.store.k * self.store.dtype.itemsize

    @property
    def panel_rows(self) -> int:
        return self.store.panel_rows

    def _aux(self) -> dict[str, np.ndarray]:
        cached = getattr(self, "_aux_cache", None)
        if cached is None:
            cached = self.store.read_aux(self.emb_id)
            object.__setattr__(self, "_aux_cache", cached)
        return cached

    @property
    def vol(self) -> float:
        return float(self._aux()["vol"])

    @property
    def deg(self) -> np.ndarray:
        return self._aux()["deg"]

    @property
    def zbar(self) -> np.ndarray:
        return self._aux()["zbar"]

    def inv_deg(self) -> np.ndarray:
        """1/deg with zero-degree nodes mapped to 0 (isolated nodes have no
        commute-time limit to correct against)."""
        deg = self.deg
        return np.where(deg > 0, 1.0 / np.maximum(deg, 1e-30), 0.0).astype(np.float32)

    def _panel_range(self, row0: int, height: int) -> tuple[int, int]:
        pr = self.store.panel_rows
        if row0 % pr or height % pr:
            raise ValueError(
                f"panel [{row0}:{row0 + height}] not panel-aligned (panel={pr})"
            )
        return row0 // pr, (row0 + height) // pr

    def read_panel(self, row0: int, height: int) -> np.ndarray:
        p_lo, p_hi = self._panel_range(row0, height)
        rows = [self.store.read_panel(self.emb_id, p) for p in range(p_lo, p_hi)]
        return rows[0] if len(rows) == 1 else np.concatenate(rows, axis=0)

    def read_panel_info(self, row0: int, height: int) -> tuple[np.ndarray, int]:
        panel = self.read_panel(row0, height)
        p_lo, p_hi = self._panel_range(row0, height)
        stored = sum(
            self.store.panel_nbytes_stored(self.emb_id, p) for p in range(p_lo, p_hi)
        )
        return panel, stored

    def read_panel_encoded_info(
        self, row0: int, height: int
    ) -> tuple[np.ndarray, int, int]:
        """Stored-form panel for on-device decode (bf16: uint16 bit patterns,
        half the decoded H2D bytes; raw: already the decoded form)."""
        if self.store.codec.name != "bf16":
            panel, stored = self.read_panel_info(row0, height)
            return panel, stored, panel.nbytes
        p_lo, p_hi = self._panel_range(row0, height)
        rows = [
            self.store.read_panel_stored(self.emb_id, p) for p in range(p_lo, p_hi)
        ]
        panel = rows[0] if len(rows) == 1 else np.concatenate(rows, axis=0)
        stored = sum(
            self.store.panel_nbytes_stored(self.emb_id, p) for p in range(p_lo, p_hi)
        )
        return panel, stored, panel.size * self.store.dtype.itemsize

    def read_rows(self, rows) -> np.ndarray:
        """Gather a few Z rows (query vectors) via panel reads on the host."""
        rows = np.asarray(rows).reshape(-1)
        pr = self.store.panel_rows
        out = np.empty((rows.size, self.store.k), self.store.dtype)
        for p in np.unique(rows // pr):
            panel = self.store.read_panel(self.emb_id, int(p))
            sel = rows // pr == p
            out[sel] = panel[rows[sel] - int(p) * pr]
        return out

    def to_numpy(self) -> np.ndarray:
        """Gather the whole sketch (tests / small n only)."""
        return np.asarray(self.read_panel(0, self.store.n))
