"""Data and traffic generators of the benchmark.

Everything here is a function of ``--seed`` and of the parameters in a
configuration file (``bench/configs/<config>.json``) and a traffic file
(``bench/workloads/<cell>.json``): the same seed gives the same records,
the same request sequence and the same values to write.

Streams of one seed keep the parts independent: stream 0 makes the
loaded records, stream 1 the request sequence (op kinds, key choices and
the values that updates write), stream 2 the absent keys that existence
checks and reads ask for, stream 3 the choices of the ``recent`` chooser
(reads of keys put earlier), stream 4 the sample of loaded records that
the reference reads back where epochs retire writes.

Epochs (traffic key ``epoch_requests``): request i of the closed loop,
counted from the first warm-up request, writes with epoch
``1 + i // epoch_requests``; loaded records are untagged (epoch 0).

The YCSB request chooser is a copy of YCSB's ``ScrambledZipfianGenerator``
(core/src/main/java/site/ycsb/generator/): a zipfian over 10^10 items
drawn by Gray et al.'s closed form, then FNV-64 of the drawn rank modulo
the record count.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
OPS = ("get", "exists", "put")


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Stream ``stream`` of ``seed``; any seed up to 2**63 works."""
    return np.random.default_rng([int(seed), int(stream)])


def split(raw: bytes, width: int) -> list[bytes]:
    return [raw[i:i + width] for i in range(0, len(raw), width)]


# ------------------------------------------------------------------ records
def fnvhash64(vals) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over an int64 array: FNV-1 over the eight
    low-order octets, then ``Math.abs`` of the signed result."""
    v = np.asarray(vals, dtype=np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= prime                       # wraps modulo 2**64, as Java's long
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def ycsb_key_names(n: int) -> list[str]:
    """YCSB's ``buildKeyName`` with insertorder=hashed: "user" + the decimal
    FNV-64 hash of the record number."""
    return ["user%d" % h for h in fnvhash64(np.arange(n)).tolist()]


def make_keys(scheme: str, n: int, key_bytes: int, rng) -> list[bytes]:
    """The loaded keys in record order.  ``random``: uniform random bytes,
    as the paper's §6 load.  ``ycsb``: SHA-256 of YCSB's key name, cut to
    ``key_bytes``, so the store's uniform keyspace sees uniform prefixes."""
    if scheme == "random":
        return split(rng.bytes(n * key_bytes), key_bytes)
    if scheme == "ycsb":
        return [hashlib.sha256(s.encode()).digest()[:key_bytes]
                for s in ycsb_key_names(n)]
    raise ValueError(f"unknown key scheme {scheme!r}")


@dataclasses.dataclass
class Dataset:
    keys: list            # record number -> key
    values: list          # record number -> loaded value


def make_dataset(cfg: dict, seed: int) -> Dataset:
    rng = rng_for(seed, 0)
    n, kb, vb = cfg["records"], cfg["key_bytes"], cfg["value_bytes"]
    keys = make_keys(cfg["key_scheme"], n, kb, rng)
    if len(set(keys)) != n:
        raise ValueError("duplicate keys drawn")
    values = split(rng.bytes(n * vb), vb)
    return Dataset(keys, values)


def absent_keys(n: int, key_bytes: int, seed: int, present) -> list[bytes]:
    """``n`` fresh random keys from stream 2, none of them in ``present``."""
    rng = rng_for(seed, 2)
    out = [k for k in split(rng.bytes(n * key_bytes), key_bytes)
           if k not in present]
    while len(out) < n:                  # a collision: 2**-256 per pair
        k = rng.bytes(key_bytes)
        if k not in present:
            out.append(k)
    return out


# ------------------------------------------------------------- key choosers
def zipfian_ranks(rng, n: int, items: int, theta: float,
                  zetan: float) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextLong`` for ``n`` draws over ``items``
    items with the precomputed ``zetan`` = zeta(items, theta)."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    ranks = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def scrambled_zipfian(rng, n: int, records: int, keys: dict) -> np.ndarray:
    """YCSB's ``ScrambledZipfianGenerator`` as ``CoreWorkload`` builds it
    for a mix without inserts: item count records + 1, and a drawn record
    number past the last loaded record is drawn again."""
    out = np.empty(0, np.int64)
    while out.size < n:
        ranks = zipfian_ranks(rng, n, keys["items"] + 1,
                              keys["zipfian_constant"], keys["zetan"])
        rec = fnvhash64(ranks) % (records + 1)
        out = np.concatenate([out, rec[rec < records]])
    return out[:n]


def choose_records(rng, n: int, records: int, keys: dict) -> np.ndarray:
    dist = keys["distribution"]
    if dist == "uniform":
        return rng.integers(0, records, n)
    if dist == "scrambled_zipfian":
        return scrambled_zipfian(rng, n, records, keys)
    raise ValueError(f"unknown key distribution {dist!r}")


def epoch_requests(cfg: dict, wl: dict):
    """The traffic's ``epoch_requests``, or None without one.  An epoch is
    a whole number of closed-loop blocks and the sequence a whole number
    of epochs, so every step writes in one epoch and a cycle of the
    sequence starts one; the ``recent`` chooser reads no further back than
    the store retains."""
    e = wl.get("epoch_requests")
    keys = wl["keys"]
    if e is None:
        if keys["distribution"] == "recent":
            raise ValueError("the recent chooser needs epoch_requests")
        return None
    n = wl["outstanding"] * wl["sequence_blocks"]
    if e <= 0 or e % wl["outstanding"] or n % e:
        raise ValueError(f"epoch_requests {e} must be a multiple of "
                         f"outstanding {wl['outstanding']} and divide the "
                         f"sequence's {n} requests")
    if keys["distribution"] == "recent":
        retain = (cfg["store"].get("prune") or {}).get("retain_epochs")
        if retain is not None and keys["within_epochs"] > retain:
            raise ValueError(f"within_epochs {keys['within_epochs']} exceeds "
                             f"retain_epochs {retain}")
    return e


def recent_sources(rng, is_put: np.ndarray, reads: np.ndarray,
                   per_epoch: int, keys: dict,
                   records: int) -> tuple[np.ndarray, np.ndarray]:
    """The ``recent`` chooser, for the reads of present keys ``reads``
    (positions, ascending): each reads a loaded record with probability
    ``loaded_share``, else a key put earlier in the same cycle of the
    sequence within the newest ``within_epochs`` epochs of its own
    (``per_epoch`` requests each), uniformly.  A read with no such put
    takes a loaded record.

    Returns (source, record) per read: the position of the put it reads,
    or -1; the loaded record it reads, or -1 (neither: no record is
    loaded, and the read asks for an absent key)."""
    puts = np.flatnonzero(is_put)
    first = np.maximum(0, (reads // per_epoch - keys["within_epochs"] + 1)
                       * per_epoch)
    lo = np.searchsorted(puts, first)
    count = np.searchsorted(puts, reads) - lo
    loaded = (rng.random(reads.size) < keys["loaded_share"]) | (count == 0)
    pick = lo + (rng.random(reads.size) * count).astype(np.int64)
    source = np.where(loaded, -1, puts[np.minimum(pick, puts.size - 1)]
                      if puts.size else -1)
    record = (rng.integers(0, records, reads.size) if records
              else np.full(reads.size, -1))
    return source, np.where(loaded, record, -1)


# ----------------------------------------------------------------- requests
@dataclasses.dataclass
class Sequence:
    """A closed loop's request sequence, cycled when a window outruns it.

    ``op[i]`` indexes ``OPS``; ``key[i]`` is the key asked for or written;
    ``record[i]`` its record number, or -1 for a key not loaded (absent,
    new, or put earlier in the sequence);
    ``value[i]`` is the value a put writes (None for reads)."""
    op: np.ndarray
    record: np.ndarray
    key: list
    value: list

    def __len__(self) -> int:
        return len(self.op)


def _block_pattern(rng, blocks: int, size: int, shares: list) -> np.ndarray:
    """``blocks`` rows of ``size`` labels, each row holding label j exactly
    round(shares[j]·size) times (the last takes the rest), rows shuffled
    independently: every block of ``outstanding`` requests has the same
    counts, so seeds differ in order and keys, not in the amount of work."""
    counts = [int(round(s * size)) for s in shares[:-1]]
    counts.append(size - sum(counts))
    if min(counts) < 0:
        raise ValueError(f"shares {shares} do not fit {size}")
    row = np.repeat(np.arange(len(counts)), counts)
    return rng.permuted(np.tile(row, (blocks, 1)), axis=1).reshape(-1)


def make_sequence(cfg: dict, wl: dict, data: Dataset, seed: int) -> Sequence:
    rng = rng_for(seed, 1)
    size, blocks = wl["outstanding"], wl["sequence_blocks"]
    n = size * blocks
    kinds = list(wl["mix"])
    op = np.array([OPS.index(k) for k in kinds], np.int8)[
        _block_pattern(rng, blocks, size, [wl["mix"][k] for k in kinds])]
    absent = _block_pattern(rng, blocks, size,
                            [1.0 - wl["absent_share"], wl["absent_share"]])
    is_put = op == OPS.index("put")
    fresh = (absent == 1) | (is_put & (wl["put_keys"] == "new"))
    source = None
    if wl["keys"]["distribution"] == "recent":
        if wl["put_keys"] != "new":
            raise ValueError("the recent chooser reads puts of new keys")
        record = np.full(n, -1, np.int64)
        reads = np.flatnonzero(~fresh & ~is_put)
        src, rec = recent_sources(rng_for(seed, 3), is_put, reads,
                                  epoch_requests(cfg, wl), wl["keys"],
                                  cfg["records"])
        record[reads] = rec
        fresh[reads[(src < 0) & (rec < 0)]] = True
        source = np.full(n, -1, np.int64)
        source[reads] = src
    else:
        record = choose_records(rng, n, cfg["records"], wl["keys"])
    record[fresh] = -1
    n_fresh = int(fresh.sum())
    extra = (absent_keys(n_fresh, cfg["key_bytes"], seed, set(data.keys))
             if n_fresh else [])
    it = iter(extra)
    keys = [data.keys[r] if r >= 0 else next(it) if f else None
            for r, f in zip(record.tolist(), fresh.tolist())]
    if source is not None:
        for j in np.flatnonzero(source >= 0).tolist():
            keys[j] = keys[source[j]]
    vb = cfg["value_bytes"]
    puts = split(rng.bytes(int(is_put.sum()) * vb), vb)
    pv = iter(puts)
    value = [next(pv) if p else None for p in is_put.tolist()]
    return Sequence(op, record, keys, value)
