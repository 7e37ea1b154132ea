"""Versioned, chunked, content-addressed weight manifests.

A published param pytree is flattened (key order = pytree flatten order,
deterministic), each leaf is encoded by the transfer codec, and the
concatenated stream is cut into fixed-size chunks.  A chunk's id is the
sha256 of its content, so:

  * integrity is checked on reassembly (``ChunkIntegrityError``);
  * chunks unchanged between versions keep their id — a pull upgraded to a
    newer version (or restarted after preemption against a warm local
    cache) re-fetches ONLY invalidated chunks;
  * delta manifests (``codec='delta-int8'``) carry int8 deltas against a
    base version the store still holds; a cold/expired base silently falls
    back to a full ``int8`` manifest (``Manifest.codec`` reflects what was
    actually encoded).

``synthetic_manifest`` fabricates the same structure from a byte count
alone so the analytic sim backend pulls through the identical chunk
scheduler (digests are deterministic pseudo-ids, payload fetches no-op).

The same plane carries more than weights: run checkpoints
(``repro.checkpoint.recovery``) serialize their journal + trainer payload
through ``build_manifest``/``assemble_manifest`` with ``codec='none'``,
inheriting chunk-level dedup (incremental checkpoints re-write only
changed chunks) and checksum-verified reassembly for free.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from repro.transfer import codec as codec_mod
from repro.transfer.codec import COMPRESSION_FACTOR


class ChunkIntegrityError(RuntimeError):
    """A chunk's bytes do not match its manifest checksum/size."""


class MissingChunkError(KeyError):
    """Reassembly attempted without all manifest chunks present."""


@dataclass(frozen=True)
class LeafSpec:
    key: str
    shape: Tuple[int, ...]
    dtype: str
    codec: str
    offset: int               # into the manifest's encoded stream
    nbytes: int


@dataclass(frozen=True)
class ChunkMeta:
    digest: str               # sha256 of content (content address)
    offset: int
    nbytes: int


@dataclass(frozen=True)
class Manifest:
    version: int
    codec: str                # codec actually encoded (after fallback)
    base_version: Optional[int]
    total_bytes: int          # encoded stream length
    chunk_bytes: int
    leaves: Tuple[LeafSpec, ...]
    chunks: Tuple[ChunkMeta, ...]

    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    def digests(self) -> List[str]:
        return [c.digest for c in self.chunks]


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def flatten_params(tree) -> "OrderedDict[str, np.ndarray]":
    import jax
    flat = OrderedDict()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[jax.tree_util.keystr(path)] = np.asarray(leaf)
    return flat


def build_manifest(version: int, flat: Mapping[str, np.ndarray], *,
                   codec: str = "none", chunk_bytes: int = 1 << 20,
                   base_flat: Optional[Mapping[str, np.ndarray]] = None,
                   base_version: Optional[int] = None,
                   leaf_codec=None):
    """Encode ``flat`` and cut it into chunks; returns (Manifest, stream).

    ``leaf_codec(key, arr) -> str`` overrides the codec per leaf (KV
    manifests quantize float pages but keep integer leaves exact)."""
    payloads, leaves, off = [], [], 0
    for key, arr in flat.items():
        lc = codec if leaf_codec is None else leaf_codec(key, arr)
        pb = codec_mod.encode_leaf(
            arr, lc, base=None if base_flat is None else base_flat[key])
        leaves.append(LeafSpec(key, tuple(arr.shape), str(arr.dtype),
                               lc, off, len(pb)))
        off += len(pb)
        payloads.append(pb)
    stream = b"".join(payloads)
    chunks = []
    for o in range(0, max(len(stream), 1), chunk_bytes):
        piece = stream[o:o + chunk_bytes]
        chunks.append(ChunkMeta(_sha(piece), o, len(piece)))
    return Manifest(version=version, codec=codec, base_version=base_version,
                    total_bytes=len(stream), chunk_bytes=chunk_bytes,
                    leaves=tuple(leaves), chunks=tuple(chunks)), stream


def synthetic_manifest(version: int, total_bytes: float, n_chunks: int, *,
                       codec: str = "none",
                       base_version: Optional[int] = None,
                       tag: str = "sim") -> Manifest:
    """Chunk-level stand-in for the sim backend: no payload, deterministic
    pseudo-digests (stable across restarts of the same version so warm
    caches resume), wire size scaled by the codec's compression factor.
    ``tag`` namespaces the pseudo-digests (weight pulls vs KV migrations)
    so unrelated synthetic manifests can never alias in a shared cache."""
    if codec == "delta-int8" and base_version is None:
        codec = "int8"
    if codec != "delta-int8":
        base_version = None
    eff = max(int(total_bytes * COMPRESSION_FACTOR[codec]), 1)
    n = max(min(n_chunks, eff), 1)      # never emit empty tail chunks
    per = -(-eff // n)
    tag = f"{tag}:v{version}" + (f":b{base_version}"
                                 if base_version is not None else "")
    chunks = tuple(ChunkMeta(f"{tag}:c{i}", i * per,
                             max(min(per, eff - i * per), 0))
                   for i in range(n))
    return Manifest(version=version, codec=codec, base_version=base_version,
                    total_bytes=eff, chunk_bytes=per, leaves=(),
                    chunks=chunks)


class ChunkStore:
    """Versioned host-side manifest + blob registry (one per WeightStore).

    Keeps the last ``history`` published param versions (delta bases),
    manifests built lazily per (version, codec, base) and their chunks in
    a content-addressed blob map; expired versions drop their manifests
    and any blobs no live manifest references.
    """

    def __init__(self, chunk_bytes: int = 1 << 20, history: int = 8):
        self.chunk_bytes = chunk_bytes
        self.history = history
        self._params: "OrderedDict[int, OrderedDict[str, np.ndarray]]" = \
            OrderedDict()
        self._manifests: Dict[Tuple, Manifest] = {}
        self._blobs: Dict[str, bytes] = {}

    # ------------------------------------------------------------------ #
    def publish(self, version: int, params) -> None:
        if version in self._params:
            self._drop_version(version)    # re-publish: stale manifests out
        self._params[version] = flatten_params(params)
        while len(self._params) > self.history:
            old, _ = self._params.popitem(last=False)
            self._drop_version(old)

    def _drop_version(self, version: int) -> None:
        """Purge manifests encoding (or encoded against) ``version`` and
        any blobs no surviving manifest references."""
        self._manifests = {k: m for k, m in self._manifests.items()
                           if version not in (m.version, m.base_version)}
        live = {c.digest for m in self._manifests.values()
                for c in m.chunks}
        self._blobs = {d: b for d, b in self._blobs.items() if d in live}

    def versions(self) -> List[int]:
        return list(self._params)

    def raw_bytes(self, version: int) -> int:
        return sum(a.nbytes for a in self._params[version].values())

    # ------------------------------------------------------------------ #
    def manifest(self, version: int, codec: str = "none",
                 base_version: Optional[int] = None) -> Manifest:
        if codec == "delta-int8" and (base_version is None
                                      or base_version not in self._params
                                      or base_version == version):
            codec, base_version = "int8", None      # cold/expired base
        if codec != "delta-int8":
            base_version = None
        key = (version, codec, base_version)
        if key not in self._manifests:
            flat = self._params[version]
            base_flat = (self._params[base_version]
                         if base_version is not None else None)
            m, stream = build_manifest(
                version, flat, codec=codec, chunk_bytes=self.chunk_bytes,
                base_flat=base_flat, base_version=base_version)
            for c in m.chunks:
                self._blobs.setdefault(c.digest,
                                       stream[c.offset:c.offset + c.nbytes])
            self._manifests[key] = m
        return self._manifests[key]

    def fetch(self, digest: str) -> Optional[bytes]:
        """Chunk payload, or None if the blob expired (manifest history
        rolled past it while a pull was in flight)."""
        return self._blobs.get(digest)

    # ------------------------------------------------------------------ #
    def assemble(self, manifest: Manifest, chunks: Mapping[str, bytes], *,
                 like=None, base_params=None, use_pallas: bool = False):
        return assemble_manifest(manifest, chunks, like=like,
                                 base_params=base_params,
                                 use_pallas=use_pallas)


def assemble_manifest(manifest: Manifest, chunks: Mapping[str, bytes], *,
                      like=None, base_params=None, use_pallas: bool = False):
    """Checksum-verify + reassemble + decode a pulled manifest.

    ``chunks``: digest -> bytes (the puller's local cache).  ``like``:
    a pytree with the target structure; when given, returns a pytree
    (leaves as jax arrays), else a flat {key: np.ndarray} dict.
    ``base_params`` is required for delta manifests — the RECEIVER's
    resident weights (the delta accumulates onto them).
    """
    buf = bytearray(manifest.total_bytes)
    for c in manifest.chunks:
        if c.digest not in chunks:
            raise MissingChunkError(c.digest)
        data = chunks[c.digest]
        if len(data) != c.nbytes or _sha(data) != c.digest:
            raise ChunkIntegrityError(
                f"chunk at offset {c.offset} fails checksum")
        buf[c.offset:c.offset + c.nbytes] = data
    base_flat = (flatten_params(base_params)
                 if base_params is not None else None)
    out = OrderedDict()
    for spec in manifest.leaves:
        payload = bytes(buf[spec.offset:spec.offset + spec.nbytes])
        base = (base_flat[spec.key]
                if spec.codec == "delta-int8" else None)
        out[spec.key] = codec_mod.decode_leaf(payload, spec, base=base,
                                              use_pallas=use_pallas)
    if like is None:
        return out
    import jax
    import jax.numpy as jnp
    treedef = jax.tree.structure(like)
    leaves = [jnp.asarray(out[jax.tree_util.keystr(p)])
              for p, _ in jax.tree_util.tree_flatten_with_path(like)[0]]
    return jax.tree.unflatten(treedef, leaves)


# --------------------------------------------------------------------------- #
# KV-migration manifests (zero-recompute migration over the chunk plane)
# --------------------------------------------------------------------------- #
# An engine KV export (``InferenceEngine.export_request_state``) travels on
# the SAME chunk plane as weight pulls: the bulk payload — unique KV pages
# plus per-slot ring/SSM rows — is flattened to per-PAGE leaves, encoded by
# the transfer codec (``none`` bit-exact, ``int8`` per-page quant for cheap
# links), chunked, and content-addressed exactly like a weight manifest, so
# the identical ``ChunkPull`` scheduler moves it and shares bandwidth with
# in-flight weight pulls.  The small host-side metadata (token history,
# page-index tables, sampling keys) rides out-of-band as ``kv_meta``.

def kv_flat(state: Mapping) -> "OrderedDict[str, np.ndarray]":
    """Flatten an engine KV export's bulk arrays into manifest leaves.

    One leaf PER PAGE per pool leaf (``kv:page:{j}:{pool-key}``) so int8
    quantization scales are per page, plus one leaf per per-slot state row
    (``kv:slot:{req_id}:{leaf-key}``)."""
    flat: "OrderedDict[str, np.ndarray]" = OrderedDict()
    for key, arr in state["pages"].items():
        arr = np.asarray(arr)
        ax = arr.ndim - 4                 # page axis (group pools lead G)
        for j in range(state["n_pages"]):
            flat[f"kv:page:{j}:{key}"] = np.take(arr, j, axis=ax)
    for rid, rows in state["slot_state"].items():
        for key, arr in rows.items():
            flat[f"kv:slot:{rid}:{key}"] = np.asarray(arr)
    return flat


def kv_meta(state: Mapping) -> Dict:
    """The out-of-band half of a KV export: everything but bulk arrays."""
    return dict(page_size=state["page_size"], n_pages=state["n_pages"],
                requests=state["requests"])


def _kv_leaf_codec(codec: str):
    def pick(key: str, arr: np.ndarray) -> str:
        if codec == "none" or not np.issubdtype(np.asarray(arr).dtype,
                                                np.floating):
            return "none"
        return "int8"
    return pick


def build_kv_manifest(mig_id: int, state: Mapping, *, codec: str = "none",
                      chunk_bytes: int = 1 << 20):
    """Manifest + blobs for one migration's KV payload.

    Returns ``(manifest, blobs, meta)``: ``blobs`` is the digest->bytes map
    the source serves during the migration (grace-period host copy), and
    ``meta`` the out-of-band metadata ``assemble_kv_state`` needs."""
    m, stream = build_manifest(mig_id, kv_flat(state), codec=codec,
                               chunk_bytes=chunk_bytes,
                               leaf_codec=_kv_leaf_codec(codec))
    blobs = {c.digest: stream[c.offset:c.offset + c.nbytes]
             for c in m.chunks}
    return m, blobs, kv_meta(state)


def assemble_kv_state(manifest: Manifest, chunks: Mapping[str, bytes],
                      meta: Mapping) -> Dict:
    """Rebuild an importable KV state from pulled chunks + metadata
    (inverse of ``build_kv_manifest`` up to codec loss)."""
    flat = assemble_manifest(manifest, chunks)
    per_page: "OrderedDict[str, Dict[int, np.ndarray]]" = OrderedDict()
    slot_state: Dict[int, Dict[str, np.ndarray]] = {}
    for key, arr in flat.items():
        if key.startswith("kv:page:"):
            _, _, j, leaf = key.split(":", 3)
            per_page.setdefault(leaf, {})[int(j)] = arr
        elif key.startswith("kv:slot:"):
            _, _, rid, leaf = key.split(":", 3)
            slot_state.setdefault(int(rid), {})[leaf] = arr
        else:
            raise KeyError(f"not a KV-manifest leaf: {key}")
    pages = {}
    for leaf, by_page in per_page.items():
        slices = [by_page[j] for j in range(len(by_page))]
        # page axis: 0 for [K, ps, dh] slices, 1 when a leading G rides
        pages[leaf] = np.stack(slices, axis=slices[0].ndim - 3)
    return dict(page_size=meta["page_size"], n_pages=meta["n_pages"],
                requests=meta["requests"], pages=pages,
                slot_state=slot_state)
