"""On-disk spill format for an E2LSHoS index (the paper's storage tier).

Layout (all section offsets page-aligned):

    [0:8)    magic  b"E2LSHSPL"
    [8:12)   format version, uint32 LE (current: 1)
    [12:16)  header JSON length, uint32 LE
    [16:20)  header JSON crc32, uint32 LE
    [20:...) header JSON (utf-8), then zero padding to the first page boundary
    ...      sections, each starting on a page boundary

The header JSON records the section table (name -> offset/shape/dtype/crc32),
the layout metadata (``block_objs``, ``lane_pad``), and the solved
``LSHParams`` (+ build ``IndexStats`` when available), so a spilled file is
self-describing: ``load_external`` rebuilds a queryable index from the path
alone.

Section split (paper Sec. 5.1/5.3 mapped onto the repo's layout):

* ``blocks`` — the bucket block store, interleaved ``[NB, 2, BLKp]`` int32
  (row g = ids row then fps row). This is the STORAGE-RESIDENT section: one
  paper "block read" = one contiguous ``2 * BLKp * 4``-byte extent, which is
  what the mmap/aio :class:`~repro.storage.blockstore.BlockStore` backends
  fetch on demand. The dedicated ids/fps interleave keeps a block read a
  single I/O, exactly like the paper's 512 B object-info blocks.
* everything else (hash family ``a/b/rm``, hash tables ``blocks_head`` /
  ``table_off`` / ``table_cnt``, the CSR derived view ``entries_id`` /
  ``entries_fp``, and the DRAM tier ``db`` / ``db_norm2``) — RESIDENT
  sections. ``load_external`` loads only the subset the external plan
  consumes (family, ``blocks_head``/``table_cnt``, DRAM tier); the CSR
  view rides the file so ``load_arrays`` can round-trip the full
  ``IndexArrays`` bit-for-bit without paying for it on every serve open.

Corruption policy: the magic/version/header crc are always verified (clear
errors instead of garbage indices); per-section crc32s are verified for
every section ``load_arrays`` materializes. The demand-paged ``blocks``
section is crc-checked only by ``load_arrays``/``verify_file`` — verifying
it on ``load_external`` would read the whole store, defeating the point of
spilling.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import zlib
from typing import Optional

import numpy as np

__all__ = ["StorageFormatError", "SpillHeader", "spill_index", "read_header",
           "load_arrays", "load_external", "verify_file", "aligned_extent",
           "spill_index_sharded", "read_manifest", "load_arrays_sharded",
           "load_external_sharded",
           "MAGIC", "FORMAT_VERSION", "PAGE_SIZE", "DIRECT_ALIGN_MIN",
           "MANIFEST_NAME", "MANIFEST_MAGIC", "MANIFEST_VERSION"]

MAGIC = b"E2LSHSPL"
FORMAT_VERSION = 1
PAGE_SIZE = 4096
# sharded spill directory (paper Sec. 7: one index, blocks striped over
# drives): MANIFEST.json + resident.e2l + one block-stripe file per shard
MANIFEST_NAME = "MANIFEST.json"
MANIFEST_MAGIC = "E2LSHSHD"
MANIFEST_VERSION = 1
# O_DIRECT read granularity floor. ALIGNMENT GUARANTEES of the format:
# every section (blocks included) starts on a PAGE_SIZE boundary and the
# file is truncated to a page boundary, so for any block row g the aligned
# covering extent [align_down(start), align_up(end)) at any alignment
# <= PAGE_SIZE lies entirely inside the file — an O_DIRECT reader never
# needs a read past EOF. Row *strides* (2 * blkp * 4 bytes) are NOT
# guaranteed device-aligned (blkp is lane-padded, not sector-padded);
# direct readers must read the covering extent — `aligned_extent` below.
DIRECT_ALIGN_MIN = 512

# resident IndexArrays leaves spilled as standalone sections (the block
# store spills as the interleaved "blocks" section instead of its
# ids_blocks/fps_blocks leaves)
_RESIDENT_FIELDS = ("a", "b", "rm", "blocks_head", "table_off", "table_cnt",
                    "entries_id", "entries_fp", "db", "db_norm2")
# ...of which plan="external" actually consumes these; load_external loads
# ONLY them (the CSR view rides the file for load_arrays round-trips, and
# reading+crc-checking it on every open would cost as much as the store)
_EXTERNAL_FIELDS = ("a", "b", "rm", "blocks_head", "table_cnt",
                    "db", "db_norm2")


class StorageFormatError(RuntimeError):
    """A spilled index file is unreadable: wrong magic, unsupported format
    version, or failed checksum."""


@dataclasses.dataclass(frozen=True)
class SpillHeader:
    """Parsed + verified file header."""

    version: int
    page_size: int
    block_objs: int
    lane_pad: int
    blkp: int                 # padded block-row width (columns per row)
    nb: int                   # block rows (incl. the spare row 0)
    sections: dict            # name -> {offset, shape, dtype, crc32, nbytes}
    params: Optional[dict]    # LSHParams asdict (radii as list) or None
    stats: Optional[dict]     # IndexStats asdict or None

    @property
    def blocks_offset(self) -> int:
        return int(self.sections["blocks"]["offset"])

    @property
    def block_row_bytes(self) -> int:
        """One block read's extent: ids row + fps row, int32."""
        return 2 * self.blkp * 4


def _page_pad(n: int, page_size: int) -> int:
    return -(-n // page_size) * page_size


def aligned_extent(offset: int, nbytes: int, align: int = DIRECT_ALIGN_MIN):
    """The aligned covering extent of ``[offset, offset + nbytes)``:
    ``(astart, alen, inner)`` with ``astart % align == 0``,
    ``alen % align == 0`` and the payload at ``[inner, inner + nbytes)`` of
    the landing buffer. This is the read shape O_DIRECT requires (the
    ``uring`` backend issues every demand read this way); the format's
    page-aligned section layout guarantees the extent stays inside the
    file for ``align <= PAGE_SIZE``."""
    astart = (int(offset) // align) * align
    alen = -(-(int(offset) + int(nbytes) - astart) // align) * align
    return astart, alen, int(offset) - astart


def _stack_blocks(arrays) -> np.ndarray:
    """The interleaved ``[NB, 2, BLKp]`` int32 block store of an
    ``IndexArrays`` (row g = ids row then fps row — one paper block read)."""
    ids_b = np.ascontiguousarray(np.asarray(arrays.ids_blocks, np.int32))
    fps_b = np.ascontiguousarray(np.asarray(arrays.fps_blocks, np.int32))
    if ids_b.shape != fps_b.shape or ids_b.ndim != 2:
        raise ValueError(f"malformed block store: {ids_b.shape} vs {fps_b.shape}")
    return np.ascontiguousarray(np.stack([ids_b, fps_b], axis=1))


def spill_index(path, arrays, *, params=None, stats=None,
                page_size: int = PAGE_SIZE) -> SpillHeader:
    """Write ``arrays`` (an ``IndexArrays``) to ``path`` in the spill format.

    ``params`` (``LSHParams``) should be provided whenever the file is meant
    to be served (``load_external`` needs it to build a ``SearchEngine``
    config); ``E2LSHIndex.spill`` passes it automatically.
    """
    blocks = _stack_blocks(arrays)
    payload = {"blocks": blocks}
    for name in _RESIDENT_FIELDS:
        payload[name] = np.ascontiguousarray(np.asarray(getattr(arrays, name)))
    return _write_spill(
        path, payload, block_objs=int(arrays.block_objs),
        lane_pad=int(arrays.lane_pad), blkp=int(blocks.shape[2]),
        nb=int(blocks.shape[0]), params=params, stats=stats,
        page_size=page_size)


def _write_spill(path, payload: dict, *, block_objs: int, lane_pad: int,
                 blkp: int, nb: int, params=None, stats=None,
                 page_size: int = PAGE_SIZE) -> SpillHeader:
    """The one spill-file writer: magic + versioned crc-guarded header +
    page-aligned crc'd sections. ``spill_index`` passes the full payload
    (blocks + every resident field); the sharded spill uses it per file
    (resident-only, or one block stripe per shard)."""
    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)

    # lay sections out page-aligned after a (generous) header page budget;
    # offsets feed the header, so compute the header size with a fixed-point
    # pass: build the JSON once with placeholder offsets to size it.
    def header_json(sections: dict) -> bytes:
        meta = dict(
            page_size=page_size,
            block_objs=int(block_objs),
            lane_pad=int(lane_pad),
            blkp=int(blkp),
            nb=int(nb),
            sections=sections,
            params=_params_dict(params),
            stats=dict(stats.__dict__) if stats is not None else None,
        )
        return json.dumps(meta, sort_keys=True).encode("utf-8")

    def section_table(base: int) -> dict:
        table = {}
        off = base
        for name, arr in payload.items():
            table[name] = dict(
                offset=off, shape=list(arr.shape), dtype=str(arr.dtype),
                nbytes=int(arr.nbytes),
                crc32=int(zlib.crc32(arr.tobytes()) & 0xFFFFFFFF),
            )
            off = _page_pad(off + arr.nbytes, page_size)
        return table

    base = _page_pad(20 + len(header_json(section_table(0))), page_size)
    # a second pass can only grow the JSON by the offset digits; re-pad once
    base2 = _page_pad(20 + len(header_json(section_table(base))), page_size)
    sections = section_table(max(base, base2))
    hdr = header_json(sections)
    first = min(sec["offset"] for sec in sections.values())
    assert 20 + len(hdr) <= first, "spill header overflowed its page budget"

    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(np.uint32(FORMAT_VERSION).tobytes())
        f.write(np.uint32(len(hdr)).tobytes())
        f.write(np.uint32(zlib.crc32(hdr) & 0xFFFFFFFF).tobytes())
        f.write(hdr)
        for name, arr in payload.items():
            f.seek(sections[name]["offset"])
            f.write(arr.tobytes())
        end = sections[name]["offset"] + payload[name].nbytes
        f.truncate(_page_pad(end, page_size))
    return read_header(path)


def _params_dict(params) -> Optional[dict]:
    if params is None:
        return None
    d = dict(dataclasses.asdict(params))
    d["radii"] = list(d["radii"])
    return d


def read_header(path) -> SpillHeader:
    """Parse and verify the file header (magic, version, crc)."""
    path = pathlib.Path(path)
    with open(path, "rb") as f:
        magic = f.read(8)
        if magic != MAGIC:
            raise StorageFormatError(
                f"{path}: not a spilled E2LSHoS index (magic {magic!r}, "
                f"expected {MAGIC!r})")
        version = int(np.frombuffer(f.read(4), np.uint32)[0])
        if version != FORMAT_VERSION:
            raise StorageFormatError(
                f"{path}: unsupported spill format version {version} "
                f"(this build reads version {FORMAT_VERSION}; re-spill the "
                "index with IndexArrays.spill)")
        hlen = int(np.frombuffer(f.read(4), np.uint32)[0])
        hcrc = int(np.frombuffer(f.read(4), np.uint32)[0])
        hdr = f.read(hlen)
    if len(hdr) != hlen or (zlib.crc32(hdr) & 0xFFFFFFFF) != hcrc:
        raise StorageFormatError(
            f"{path}: corrupted header (crc mismatch) — the file is "
            "truncated or damaged; re-spill the index")
    meta = json.loads(hdr.decode("utf-8"))
    return SpillHeader(
        version=version, page_size=int(meta["page_size"]),
        block_objs=int(meta["block_objs"]), lane_pad=int(meta["lane_pad"]),
        blkp=int(meta["blkp"]), nb=int(meta["nb"]),
        sections=meta["sections"], params=meta.get("params"),
        stats=meta.get("stats"),
    )


def _read_section(path, hdr: SpillHeader, name: str, *,
                  verify: bool = True) -> np.ndarray:
    sec = hdr.sections[name]
    arr = np.fromfile(path, dtype=np.dtype(sec["dtype"]),
                      count=int(np.prod(sec["shape"], dtype=np.int64)),
                      offset=int(sec["offset"]))
    if arr.nbytes != sec["nbytes"]:
        raise StorageFormatError(
            f"{path}: section {name!r} truncated "
            f"({arr.nbytes} of {sec['nbytes']} bytes)")
    if verify and (zlib.crc32(arr.tobytes()) & 0xFFFFFFFF) != sec["crc32"]:
        raise StorageFormatError(
            f"{path}: section {name!r} failed its crc32 check — the file "
            "is damaged; re-spill the index")
    return arr.reshape(sec["shape"])


def verify_file(path) -> SpillHeader:
    """Full integrity pass: header + every section crc (blocks included)."""
    hdr = read_header(path)
    for name in hdr.sections:
        _read_section(path, hdr, name, verify=True)
    return hdr


def load_arrays(path):
    """Materialize the full in-memory ``IndexArrays`` from a spilled file
    (every leaf crc-verified) — the bit-for-bit round-trip counterpart of
    ``IndexArrays.spill``."""
    import jax.numpy as jnp

    from ..core.index import IndexArrays

    hdr = read_header(path)
    blocks = _read_section(path, hdr, "blocks")
    resident = {name: _read_section(path, hdr, name)
                for name in _RESIDENT_FIELDS}
    return IndexArrays(
        ids_blocks=jnp.asarray(blocks[:, 0]),
        fps_blocks=jnp.asarray(blocks[:, 1]),
        **{name: jnp.asarray(arr) for name, arr in resident.items()},
        block_objs=hdr.block_objs, lane_pad=hdr.lane_pad,
    )


def load_external(path, *, backend: str = "aio", qd: int = 16,
                  cache_rows: Optional[int] = None, direct: bool = True,
                  strict: bool = False, prefetch_depth: int = 1):
    """Open a spilled index for external-memory querying.

    Hash tables, family params, the CSR view, and the DRAM tier load
    resident; the block store stays on disk behind the selected
    :class:`~repro.storage.blockstore.BlockStore` backend (``mem`` — the
    in-memory parity oracle; ``mmap`` — synchronous QD1 page-cache reads;
    ``aio`` — ``qd``-way pread fan-out with a clock page cache of
    ``cache_rows`` block rows; ``uring`` — O_DIRECT demand reads with
    ``qd`` in flight, submitted through io_uring or, without it, a
    ``qd``-wide ``preadv`` pool; only where neither io_uring nor O_DIRECT
    works does it fall back to ``aio``, or raise under ``strict``).
    ``direct=False`` keeps uring on buffered reads through the ring;
    ``prefetch_depth`` is how many chain steps of the next rung the
    external plan pushes into the store's queue under device compute.
    Returns an :class:`~repro.storage.external.ExternalIndex` that
    ``SearchEngine`` serves under ``plan="external"``.
    """
    import jax.numpy as jnp

    from ..core.probabilities import LSHParams
    from .blockstore import make_store
    from .external import ExternalIndex

    hdr = read_header(path)
    if hdr.params is None:
        raise StorageFormatError(
            f"{path}: spilled without LSHParams — serve it by spilling via "
            "E2LSHIndex.spill (or IndexArrays.spill(..., params=...))")
    pdict = dict(hdr.params)
    pdict["radii"] = tuple(pdict["radii"])
    params = LSHParams(**pdict)
    resident = {name: _read_section(path, hdr, name)
                for name in _EXTERNAL_FIELDS}
    store = make_store(backend, path, hdr, qd=qd, cache_rows=cache_rows,
                       direct=direct, strict=strict)
    stats = None
    if hdr.stats is not None:
        from ..core.index import IndexStats
        stats = IndexStats(**hdr.stats)
    return ExternalIndex(
        params=params,
        a=jnp.asarray(resident["a"]), b=jnp.asarray(resident["b"]),
        rm=jnp.asarray(resident["rm"]),
        blocks_head=jnp.asarray(resident["blocks_head"]),
        table_cnt=jnp.asarray(resident["table_cnt"]),
        db=jnp.asarray(resident["db"]),
        db_norm2=jnp.asarray(resident["db_norm2"]),
        block_objs=hdr.block_objs, lane_pad=hdr.lane_pad, blkp=hdr.blkp,
        store=store, path=str(path), stats=stats,
        prefetch_depth=int(prefetch_depth),
    )


# --------------------------------------------------------------------------
# Sharded spill: ONE global index, block store striped over per-shard files
# (the paper's multi-drive layout, Sec. 7 / Fig. 15 — hash tables resident,
# blocks distributed over drives). Stripe policy: round-robin by block row,
# global row g lives in shard g % num_shards at local row g // num_shards.
# --------------------------------------------------------------------------

def spill_index_sharded(path, arrays, num_shards: int, *, params=None,
                        stats=None, page_size: int = PAGE_SIZE) -> dict:
    """Write ``arrays`` as a sharded spill DIRECTORY at ``path``:

    * ``resident.e2l`` — every resident section (hash family, tables, CSR
      view, DRAM tier) in the spill-file format, no block store;
    * ``shard-NNN.e2l`` — one spill-file per shard holding that shard's
      round-robin block stripe as its ``blocks`` section;
    * ``MANIFEST.json`` — versioned, crc-guarded manifest tying them
      together (stripe policy, global row count, per-shard file table).

    Every file keeps the single-file format's guarantees (magic, versioned
    crc-guarded header, page-aligned crc'd sections), so shard files serve
    any ``BlockStore`` backend unchanged. Returns the manifest payload.
    """
    path = pathlib.Path(path)
    sh = int(num_shards)
    if sh < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    blocks = _stack_blocks(arrays)
    nb = int(blocks.shape[0])
    if sh > nb:
        raise ValueError(
            f"cannot stripe {nb} block rows over {sh} shards (at most one "
            "shard per block row)")
    path.mkdir(parents=True, exist_ok=True)
    resident = {name: np.ascontiguousarray(np.asarray(getattr(arrays, name)))
                for name in _RESIDENT_FIELDS}
    common = dict(block_objs=int(arrays.block_objs),
                  lane_pad=int(arrays.lane_pad), blkp=int(blocks.shape[2]),
                  page_size=page_size)
    _write_spill(path / "resident.e2l", resident, nb=nb, params=params,
                 stats=stats, **common)
    shards = []
    for s in range(sh):
        stripe = np.ascontiguousarray(blocks[s::sh])
        fname = f"shard-{s:03d}.e2l"
        _write_spill(path / fname, {"blocks": stripe},
                     nb=int(stripe.shape[0]), **common)
        shards.append(dict(file=fname, nb=int(stripe.shape[0])))
    payload = dict(
        num_shards=sh,
        stripe=dict(policy="round_robin", chunk_rows=1),
        nb_global=nb, blkp=int(blocks.shape[2]),
        block_objs=int(arrays.block_objs), lane_pad=int(arrays.lane_pad),
        resident="resident.e2l", shards=shards,
    )
    body = json.dumps(payload, sort_keys=True).encode("utf-8")
    manifest = dict(magic=MANIFEST_MAGIC, version=MANIFEST_VERSION,
                    crc32=int(zlib.crc32(body) & 0xFFFFFFFF), payload=payload)
    (path / MANIFEST_NAME).write_text(
        json.dumps(manifest, sort_keys=True, indent=1))
    return payload


def read_manifest(path) -> dict:
    """Parse and verify a sharded spill's ``MANIFEST.json`` (magic, version,
    payload crc); returns the manifest payload. ``path`` is the spill
    directory (or the manifest file itself)."""
    path = pathlib.Path(path)
    man_path = path / MANIFEST_NAME if path.is_dir() else path
    try:
        man = json.loads(man_path.read_text())
    except FileNotFoundError:
        raise StorageFormatError(
            f"{path}: not a sharded spill (no {MANIFEST_NAME}; single-file "
            "spills open with load_external)") from None
    except (OSError, json.JSONDecodeError) as e:
        raise StorageFormatError(
            f"{man_path}: unreadable sharded-spill manifest ({e})") from None
    if man.get("magic") != MANIFEST_MAGIC:
        raise StorageFormatError(
            f"{man_path}: not a sharded spill manifest "
            f"(magic {man.get('magic')!r}, expected {MANIFEST_MAGIC!r})")
    if int(man.get("version", -1)) != MANIFEST_VERSION:
        raise StorageFormatError(
            f"{man_path}: unsupported manifest version {man.get('version')} "
            f"(this build reads version {MANIFEST_VERSION}; re-spill the "
            "index with spill_index_sharded)")
    payload = man.get("payload")
    body = json.dumps(payload, sort_keys=True).encode("utf-8")
    if (zlib.crc32(body) & 0xFFFFFFFF) != int(man.get("crc32", -1)):
        raise StorageFormatError(
            f"{man_path}: corrupted manifest (crc mismatch) — the manifest "
            "was edited or damaged; re-spill the index")
    return payload


def _shard_headers(path: pathlib.Path, man: dict) -> list:
    """Open + cross-check every shard file against the manifest."""
    headers = []
    for s, entry in enumerate(man["shards"]):
        sp = path / entry["file"]
        try:
            shdr = read_header(sp)
        except FileNotFoundError:
            raise StorageFormatError(
                f"{path}: shard file {entry['file']!r} is missing — the "
                "sharded spill is incomplete; re-spill the index") from None
        if shdr.nb != int(entry["nb"]) or shdr.blkp != int(man["blkp"]):
            raise StorageFormatError(
                f"{sp}: shard {s} disagrees with the manifest "
                f"(nb {shdr.nb} vs {entry['nb']}, blkp {shdr.blkp} vs "
                f"{man['blkp']}) — mixed spill generations; re-spill")
        headers.append(shdr)
    return headers


def load_arrays_sharded(path):
    """Materialize the full ``IndexArrays`` from a sharded spill directory
    (every section crc-verified, stripes re-interleaved) — the bit-for-bit
    round-trip counterpart of ``spill_index_sharded``."""
    import jax.numpy as jnp

    from ..core.index import IndexArrays

    path = pathlib.Path(path)
    man = read_manifest(path)
    rhdr = read_header(path / man["resident"])
    resident = {name: _read_section(path / man["resident"], rhdr, name)
                for name in _RESIDENT_FIELDS}
    sh = int(man["num_shards"])
    blocks = np.empty((int(man["nb_global"]), 2, int(man["blkp"])),
                      dtype=np.int32)
    for s, shdr in enumerate(_shard_headers(path, man)):
        blocks[s::sh] = _read_section(path / man["shards"][s]["file"],
                                      shdr, "blocks")
    return IndexArrays(
        ids_blocks=jnp.asarray(blocks[:, 0]),
        fps_blocks=jnp.asarray(blocks[:, 1]),
        **{name: jnp.asarray(arr) for name, arr in resident.items()},
        block_objs=rhdr.block_objs, lane_pad=rhdr.lane_pad,
    )


def load_external_sharded(path, *, backend: str = "aio", qd: int = 16,
                          cache_rows: Optional[int] = None,
                          direct: bool = True, strict: bool = False,
                          prefetch_depth: int = 1):
    """Open a sharded spill directory for external-memory querying under
    ``plan="sharded_external"``.

    Resident sections load from ``resident.e2l``; each shard's block stripe
    stays on disk behind its OWN :class:`~repro.storage.blockstore.BlockStore`
    (same ``backend``/``qd``/``direct``/``strict`` semantics as
    ``load_external``, ``REPRO_STORE_BACKEND`` honored per store), striped
    back together by a :class:`~repro.storage.sharded.StripedBlockStore` —
    per-shard caches and ledgers, one rolled-up measured-N_io view.
    ``cache_rows`` is the TOTAL cache budget, divided evenly over shards.
    Returns a :class:`~repro.storage.sharded.ShardedExternalIndex`.
    """
    import jax.numpy as jnp

    from ..core.probabilities import LSHParams
    from .blockstore import make_store
    from .sharded import ShardedExternalIndex, StripedBlockStore

    path = pathlib.Path(path)
    man = read_manifest(path)
    rhdr = read_header(path / man["resident"])
    if rhdr.params is None:
        raise StorageFormatError(
            f"{path}: spilled without LSHParams — re-spill via "
            "ShardedIndexArrays.spill or spill_index_sharded(..., params=...)")
    pdict = dict(rhdr.params)
    pdict["radii"] = tuple(pdict["radii"])
    params = LSHParams(**pdict)
    resident = {name: _read_section(path / man["resident"], rhdr, name)
                for name in _EXTERNAL_FIELDS}
    sh = int(man["num_shards"])
    per_cache = (None if cache_rows is None
                 else max(1, -(-int(cache_rows) // sh)))
    stores = []
    try:
        for s, shdr in enumerate(_shard_headers(path, man)):
            stores.append(make_store(
                backend, path / man["shards"][s]["file"], shdr, qd=qd,
                cache_rows=per_cache, direct=direct, strict=strict))
    except Exception:
        for st in stores:
            st.close()
        raise
    store = StripedBlockStore(stores, nb=int(man["nb_global"]),
                              blkp=int(man["blkp"]))
    stats = None
    if rhdr.stats is not None:
        from ..core.index import IndexStats
        stats = IndexStats(**rhdr.stats)
    return ShardedExternalIndex(
        params=params,
        a=jnp.asarray(resident["a"]), b=jnp.asarray(resident["b"]),
        rm=jnp.asarray(resident["rm"]),
        blocks_head=jnp.asarray(resident["blocks_head"]),
        table_cnt=jnp.asarray(resident["table_cnt"]),
        db=jnp.asarray(resident["db"]),
        db_norm2=jnp.asarray(resident["db_norm2"]),
        block_objs=rhdr.block_objs, lane_pad=rhdr.lane_pad, blkp=rhdr.blkp,
        store=store, path=str(path), stats=stats,
        prefetch_depth=int(prefetch_depth),
        num_shards=sh, manifest=man,
    )
