"""Page wire format — Batch ⇄ bytes for the shuffle and client protocol.

Reference: execution/buffer/PagesSerde.java:44 + the per-block encodings
(spi/block/*Encoding.java) with optional LZ4, used by the HTTP pull shuffle
(SerializedPage) and spill files.

TPU-native redesign: pages are host-side only at exchange boundaries; the
format is flat little-endian column buffers (exactly the device layout, so
deserialize is a zero-copy-ish np.frombuffer + device_put) plus the string
dictionaries, with optional zstd compression. Live rows are compacted before
serialization — wire pages carry no padding.

A consumer on the producer's own device takes no page: `handoff_batch` gives
it what a decoded page would, without the batch leaving the device.
"""

from __future__ import annotations

import json
import struct
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column, round_up_capacity
from presto_tpu.dictionary import Dictionary
from presto_tpu.obs import trace as _obs_trace
from presto_tpu.types import Type, parse_type

_MAGIC = b"PTP1"
_FLAG_ZSTD = 1

# dictionaries at or under this many values are always inlined on the wire:
# the ref+fetch round trip costs more than the payload
_DICT_INLINE_MAX = 64


class TaggedBatch(Batch):
    """A deserialized page carrying its producer's radix partition id.

    Serde-level only: consumers that radix-partition check `radix` via
    getattr and strip to a plain Batch before any jitted code — the pytree
    registration is type-exact, so this subclass must never reach jit.
    `radix` is (partition_id, num_partitions, key_names)."""

    __slots__ = ("radix",)

    def __init__(self, names, types, columns, live, dicts, radix):
        super().__init__(names, types, columns, live, dicts)
        self.radix = radix

try:
    import zstandard as _zstd
except Exception:  # pragma: no cover
    _zstd = None

import threading as _threading

_TLS = _threading.local()


def _zc():
    """Per-thread compressor: zstd (de)compressor objects are not safe for
    concurrent use, and worker tasks serialize pages from many threads."""
    if _zstd is None:
        return None
    c = getattr(_TLS, "zc", None)
    if c is None:
        c = _TLS.zc = _zstd.ZstdCompressor(level=1)
    return c


def _zd():
    if _zstd is None:
        return None
    d = getattr(_TLS, "zd", None)
    if d is None:
        d = _TLS.zd = _zstd.ZstdDecompressor()
    return d


# -- dictionary interning ----------------------------------------------------
# Dictionaries hash by identity (jit cache keys off the object). Pages arrive
# from many peers carrying the same logical dictionary; interning returns one
# canonical object per content so (a) codes from different workers are
# mergeable and (b) jitted programs don't retrace per page.
#
# Keys are strong content digests (collisions would silently break the
# one-object-per-content invariant) and the table is a bounded LRU: computed
# string columns produce a fresh Dictionary per batch, so an unbounded table
# leaks in a long-lived worker.
import hashlib as _hashlib
from collections import OrderedDict as _OrderedDict

_DICT_INTERN: "_OrderedDict[bytes, Dictionary]" = _OrderedDict()
_DICT_INTERN_CAP = 4096
_DICT_INTERN_LOCK = _threading.Lock()


def _dict_content_key(values: np.ndarray) -> bytes:
    h = _hashlib.sha256()
    if values.dtype.kind not in ("O", "U", "S"):
        h.update(values.tobytes())
    else:
        h.update("\x00".join(map(str, values)).encode("utf-8", "surrogatepass"))
    return h.digest()


def _intern_put(key: bytes, make: "Callable[[], Dictionary]") -> Dictionary:
    """Atomic get-or-insert + LRU bump; exchange fetcher threads intern
    concurrently and must agree on ONE canonical object per content."""
    with _DICT_INTERN_LOCK:
        hit = _DICT_INTERN.get(key)
        if hit is not None:
            _DICT_INTERN.move_to_end(key)
            return hit
        d = make()
        _DICT_INTERN[key] = d
        while len(_DICT_INTERN) > _DICT_INTERN_CAP:
            _DICT_INTERN.popitem(last=False)
        return d


def intern_dictionary(values: np.ndarray) -> Dictionary:
    values = np.asarray(values)
    return _intern_put(_dict_content_key(values), lambda: Dictionary(values))


def register_dictionary(d: Dictionary) -> Dictionary:
    """Intern a producer-side dictionary BEFORE its pages hit the wire, so
    in-process consumers deserialize to the identical object (keeping jit
    caches warm across the exchange). Returns the canonical object for its
    content, the one a decode returns; memoized per Dictionary object."""
    hit = d._memo.get("__interned")
    if hit is not None:
        return hit
    out = _intern_put(_dict_content_key(d.values), lambda: d)
    d._memo["__interned"] = out
    return out


def _intern_hit(key: bytes) -> Optional[Dictionary]:
    with _DICT_INTERN_LOCK:
        hit = _DICT_INTERN.get(key)
        if hit is not None:
            _DICT_INTERN.move_to_end(key)
        return hit


def lookup_dictionary(digest_hex: str) -> Optional[List[str]]:
    """Side-channel hook for the /v1/dict endpoint: the value list for an
    interned dictionary digest, or None when evicted / never seen (the
    producer interns every dictionary it sends by ref, so a miss means LRU
    eviction — the consumer should fail the page, not guess)."""
    try:
        key = bytes.fromhex(digest_hex)
    except ValueError:
        return None
    d = _intern_hit(key)
    if d is None:
        return None
    return [str(v) for v in d.values]


def _pack_bits(mask: np.ndarray) -> bytes:
    return np.packbits(mask.astype(np.uint8)).tobytes()


def _unpack_bits(data: bytes, n: int) -> np.ndarray:
    return np.unpackbits(np.frombuffer(data, np.uint8), count=n).astype(bool)


def _planes(c: Column) -> tuple:
    return (c.values, c.validity, c.hi, c.sizes, c.evalid, c.keys)


def _flat(b: Batch) -> list:
    """`b.live`, then each column's planes that are there."""
    return [b.live] + [p for c in b.columns for p in _planes(c)
                       if p is not None]


def _regroup(flat: list, columns) -> tuple:
    """(live, columns) again from `_flat`'s list, or from one of arrays made
    from it in its order."""
    it = iter(flat)
    live = next(it)
    return live, [Column(*(None if p is None else next(it)
                           for p in _planes(c))) for c in columns]


def _pack(planes: list, cap: int) -> list:
    """`_flat`'s planes gathered into `cap` lanes: the live lanes first, in
    their order, then zeros (what `_decode` pads with). Slot j takes the
    lane where the running live count reaches j + 1: a binary search, which
    the TPU's compiler builds in seconds where a sort of 2^17 lanes takes
    it half a minute."""
    live = planes[0]
    seen = jnp.cumsum(live, dtype=jnp.int32)
    perm = jnp.searchsorted(seen, jnp.arange(1, cap + 1, dtype=jnp.int32))
    perm = jnp.minimum(perm, live.shape[0] - 1)
    keep = jnp.arange(cap) < seen[-1]
    return [jnp.where(keep.reshape((cap,) + (1,) * (p.ndim - 1)), p[perm],
                      jnp.zeros((), p.dtype)) for p in planes]


_JIT_PACK = jax.jit(_pack, static_argnums=1)


def handoff_batch(b: Batch, n: int, radix: Optional[tuple] = None) -> Batch:
    """`b`, whose live count is `n`, as a consumer on the same device takes
    it in place of the page `serialize_batch(b, radix=radix)`: the same
    rows in the same order, the same dictionary objects and radix tag that
    `deserialize_batch` gives, in the same number of lanes. A batch already
    of `round_up_capacity(max(n, 1))` lanes goes as it is; any other is
    gathered on the device (`_pack`). Nothing is copied to the host."""
    cap = round_up_capacity(max(n, 1))
    planes = _flat(b)
    if cap != b.capacity:
        planes = _JIT_PACK(planes, cap)
    else:
        planes = [p if isinstance(p, jax.Array) else jnp.asarray(p)
                  for p in planes]
    live, cols = _regroup(planes, b.columns)
    dicts = {dk: register_dictionary(b.dicts[dk]) for name in b.names
             for dk in (name, name + "#keys") if dk in b.dicts}
    if radix is not None:
        return TaggedBatch(b.names, b.types, cols, live, dicts, radix)
    return Batch(b.names, b.types, cols, live, dicts)


def serialize_batch(b: Batch, compress: bool = True,
                    radix: Optional[tuple] = None,
                    dict_refs: bool = False, tracer=None) -> bytes:
    """Compact live rows and serialize. Safe to call on device or host arrays.

    radix: (partition_id, num_partitions, key_names) — stamps the page so an
    aligned consumer skips its re-partition sort (deserializes TaggedBatch).
    dict_refs: large dictionaries go on the wire as a content digest instead
    of their full value list; the consumer resolves a miss once through the
    /v1/dict side channel. Leave False for spill files, which must stay
    self-contained.
    tracer: an exchange's sink hands its task's tracer, and the page's trip
    is three phases of it: `page_ready` (the device finishing the batch;
    enabled tracers only, so a disabled one adds no call), `page_fetch`
    (each plane copied to the host whole, `items` the bytes that came from
    the device) and `page_encode` (the mask, the buffers, the header, zstd;
    `items` the page's bytes)."""
    tracer = tracer or _obs_trace.NOOP
    device = _flat(b)
    if tracer.enabled:
        with tracer.phase("page_ready"):
            jax.block_until_ready(device)
    with tracer.phase("page_fetch") as ph:
        host = [np.asarray(p) for p in device]
        # np.asarray hands a host array back as it is: no copy, no bytes
        ph.items = sum(h.nbytes for h, p in zip(host, device) if h is not p)
    with tracer.phase("page_encode") as ph:
        page = _encode(b, *_regroup(host, b.columns), radix, dict_refs,
                       compress)
        ph.items = len(page)
    return page


def _encode(b: Batch, live: np.ndarray, cols: List[Column], radix,
            dict_refs: bool, compress: bool) -> bytes:
    """The page of `b`, whose planes `live` and `cols` hold on the host."""
    n = int(live.sum())
    header = {"n": n, "names": list(b.names), "types": [str(t) for t in b.types],
              "validity": [], "limbs": [], "struct": [], "dicts": {}}
    if radix is not None:
        r, num, keys = radix
        header["radix"] = [int(r), int(num), list(keys)]
    buffers: List[bytes] = []
    for name, t, c in zip(b.names, b.types, cols):
        buffers.append(np.ascontiguousarray(c.values[live]).tobytes())
        if c.validity is not None:
            header["validity"].append(True)
            buffers.append(_pack_bits(c.validity[live]))
        else:
            header["validity"].append(False)
        if c.hi is not None:
            # long-decimal high limb rides as a second int64 buffer
            header["limbs"].append(True)
            buffers.append(np.ascontiguousarray(c.hi[live]).tobytes())
        else:
            header["limbs"].append(False)
        if c.sizes is not None:
            # structural planes: [w, has_evalid, has_keys, keys_dtype]
            # (values buffer above is the [n, w] element plane, row-major)
            w = int(c.values.shape[1])
            has_ev = c.evalid is not None
            has_k = c.keys is not None
            header["struct"].append(
                [w, has_ev, has_k,
                 str(c.keys.dtype) if has_k else None])
            buffers.append(np.ascontiguousarray(c.sizes[live]).tobytes())
            if has_ev:
                buffers.append(_pack_bits(c.evalid[live].reshape(-1)))
            if has_k:
                buffers.append(np.ascontiguousarray(c.keys[live]).tobytes())
        else:
            header["struct"].append(None)
        for dk in (name, name + "#keys"):
            if dk not in b.dicts:
                continue
            d = register_dictionary(b.dicts[dk])
            if dict_refs and len(d.values) > _DICT_INLINE_MAX:
                header["dicts"][dk] = {
                    "ref": _dict_content_key(d.values).hex(),
                    "len": len(d.values)}
            else:
                header["dicts"][dk] = [str(v) for v in d.values]
    payload = b"".join(buffers)
    flags = 0
    zc = _zc()
    if compress and zc is not None and len(payload) > 512:
        payload = zc.compress(payload)
        flags |= _FLAG_ZSTD
    hj = json.dumps(header, separators=(",", ":")).encode()
    return _MAGIC + struct.pack("<BII", flags, len(hj), len(payload)) + hj + payload


def deserialize_batch(data: bytes, capacity: Optional[int] = None,
                      dict_resolver: Optional[Callable[[str], List[str]]]
                      = None, host: bool = False, tracer=None) -> Batch:
    """`host=True` keeps every plane a numpy array of exactly the page's
    rows (no padding, nothing uploaded): what a consumer that packs pages
    into batches of its own capacity wants (spiller.pack_pages).
    tracer: an exchange's consumer hands its tracer, and the page's trip
    ends in two phases of it: `page_decode` (header, zstd, the padded host
    planes, the dictionaries; `items` the page's bytes) and `page_upload`
    (each plane put on the device; `items` the bytes put)."""
    tracer = tracer or _obs_trace.NOOP
    with tracer.phase("page_decode", items=len(data)):
        b = _decode(data, capacity, dict_resolver, host)
    if host:
        return b
    with tracer.phase("page_upload") as ph:
        planes = _flat(b)
        ph.items = sum(p.nbytes for p in planes)
        live, cols = _regroup([jnp.asarray(p) for p in planes], b.columns)
    if isinstance(b, TaggedBatch):
        return TaggedBatch(b.names, b.types, cols, live, b.dicts, b.radix)
    return Batch(b.names, b.types, cols, live, b.dicts)


def _decode(data: bytes, capacity: Optional[int], dict_resolver,
            host: bool) -> Batch:
    """The page's batch with every plane a numpy array: padded to
    `capacity` (the page's rows rounded up by default), or of exactly the
    page's rows where `host`."""
    assert data[:4] == _MAGIC, "bad page magic"
    flags, hlen, plen = struct.unpack_from("<BII", data, 4)
    off = 4 + 9
    header = json.loads(data[off:off + hlen])
    payload = data[off + hlen:off + hlen + plen]
    if flags & _FLAG_ZSTD:
        payload = _zd().decompress(payload)
    n = header["n"]
    cap = capacity or (n if host else round_up_capacity(max(n, 1)))
    names = header["names"]
    types = [parse_type(s) for s in header["types"]]

    cols = []
    pos = 0
    limbs = header.get("limbs") or [False] * len(names)
    structs = header.get("struct") or [None] * len(names)
    for name, t, has_valid, has_hi, st in zip(names, types,
                                              header["validity"], limbs,
                                              structs):
        dt = np.dtype(str(t.dtype))
        w = st[0] if st is not None else None
        count = n * w if w is not None else n
        vals = np.frombuffer(payload, dt, count=count, offset=pos)
        pos += count * dt.itemsize
        if w is not None:
            buf = np.zeros((cap, w), dtype=dt)
            buf[:n] = vals.reshape(n, w)
        else:
            buf = np.zeros(cap, dtype=dt)
            buf[:n] = vals
        if has_valid:
            vb = (n + 7) // 8
            valid = _unpack_bits(payload[pos:pos + vb], n)
            pos += vb
            valid_arr = np.zeros(cap, dtype=bool)
            valid_arr[:n] = valid
        else:
            valid_arr = None
        hi_arr = None
        if has_hi:
            hi = np.frombuffer(payload, np.int64, count=n, offset=pos)
            pos += n * 8
            hi_arr = np.zeros(cap, dtype=np.int64)
            hi_arr[:n] = hi
        sizes_arr = evalid_arr = keys_arr = None
        if st is not None:
            _, has_ev, has_k, kdt = st
            sizes = np.frombuffer(payload, np.int32, count=n, offset=pos)
            pos += n * 4
            sizes_arr = np.zeros(cap, np.int32)
            sizes_arr[:n] = sizes
            if has_ev:
                eb = (n * w + 7) // 8
                ev = _unpack_bits(payload[pos:pos + eb], n * w)
                pos += eb
                evalid_arr = np.zeros((cap, w), bool)
                evalid_arr[:n] = ev.reshape(n, w)
            if has_k:
                kd = np.dtype(kdt)
                keys = np.frombuffer(payload, kd, count=n * w, offset=pos)
                pos += n * w * kd.itemsize
                keys_arr = np.zeros((cap, w), kd)
                keys_arr[:n] = keys.reshape(n, w)
        cols.append(Column(buf, valid_arr, hi_arr,
                           sizes_arr, evalid_arr, keys_arr))
    live = np.zeros(cap, dtype=bool)
    live[:n] = True
    dicts = {}
    for k, v in header["dicts"].items():
        if isinstance(v, dict):
            # by-ref dictionary: the in-process intern table almost always
            # has it (the producer interned it before sending); a genuine
            # miss goes through the side channel exactly once
            key = bytes.fromhex(v["ref"])
            d = _intern_hit(key)
            if d is None:
                if dict_resolver is None:
                    raise ValueError(
                        "page references dictionary "
                        f"{v['ref'][:12]} with no resolver available")
                vals = np.asarray(dict_resolver(v["ref"]), dtype=object)
                d = _intern_put(key, lambda vals=vals: Dictionary(vals))
            dicts[k] = d
        else:
            dicts[k] = intern_dictionary(np.asarray(v, dtype=object))
    rd = header.get("radix")
    if rd is not None:
        return TaggedBatch(names, types, cols, live, dicts,
                           (int(rd[0]), int(rd[1]), tuple(rd[2])))
    return Batch(names, types, cols, live, dicts)
