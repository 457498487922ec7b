"""Dictionary encoding: string columns on the device tier.

TPUs have no string dtype, so a string column becomes an int32 CODE column
plus a host-side dictionary sidecar on the Block (block.Block.dicts):
``dicts[name][code] == original string``. The dictionary is SORTED, so codes
are RANK codes — comparing codes compares strings (lexicographically, by code
point), which means sort / take_ordered / min / max run on the codes directly
with no extra pass. Equality ops (group_by, join, distinct, count_by_key)
ride the existing exchange/segment-reduce kernels unchanged: codes are just
another int32 column.

A dictionary is a `StringDict`: one arrow string (or binary) array, offsets
plus UTF-8 bytes, so its host memory is about the bytes of its strings — not
the longest string's width times the entry count, as a fixed-width numpy
'<U' array would hold (400 B an entry for a 100-character URL). It indexes
like the numpy array it replaces: an int gives one string, an array of codes
gives the strings as a '<U' (or 'S') array, `np.asarray` the whole.

Two blocks encoded independently carry DIFFERENT dictionaries, so their
codes are not comparable; dense_rdd._DictUnifyRDD remaps both sides onto
one merged dictionary (`merge_dicts` here, memoized on the two dictionaries'
identity, + one device remap program there) lazily before any keyed binary
op. Decode happens only at the collect boundary (block._decode_dict_cols).

Hash contract note (CLAUDE.md): HOST placement keeps splitmix64 on the
string bytes; DEVICE bucketing hashes the codes. Placement may differ
between tiers — only results must match, and the parity tests assert they
do.

This is the one module of the device tier that may hold object arrays
(vegalint VG020): it turns them into codes and dictionaries, and what it
hands out is int32 codes and StringDicts.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from vega_tpu.tpu import spans

CODE_DTYPE = np.int32


def dict_enabled() -> bool:
    """Configuration.dense_dict_enabled, read lazily (env import cycles;
    callers may run before a Context exists)."""
    from vega_tpu.env import Env

    return bool(getattr(Env.get().conf, "dense_dict_enabled", True))


def dict_capacity() -> int:
    """Smallest staged unification remap table
    (Configuration.dense_dict_capacity): a table stages at this many entries,
    or at the next power of two that holds the whole dictionary."""
    from vega_tpu.env import Env

    return int(getattr(Env.get().conf, "dense_dict_capacity", 65536))


class StringDict:
    """A sorted dictionary of distinct strings (or bytes), held as one arrow
    array. `arrow` is a string, large_string, binary or large_binary array
    with no nulls."""

    def __init__(self, arrow):
        self.arrow = arrow

    @property
    def is_bytes(self) -> bool:
        import pyarrow as pa

        return pa.types.is_binary(self.arrow.type) \
            or pa.types.is_large_binary(self.arrow.type)

    @property
    def nbytes(self) -> int:
        """Host bytes: the strings' bytes and their offsets."""
        return self.arrow.nbytes

    def __len__(self) -> int:
        return len(self.arrow)

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            i = int(idx)
            if i < 0:
                i += len(self)
            if not 0 <= i < len(self):
                raise IndexError(f"code {int(idx)} outside a dictionary of "
                                 f"{len(self)}")
            v = self.arrow[i].as_py()
            return np.bytes_(v) if self.is_bytes else np.str_(v)
        return self.take(idx)

    def take(self, codes) -> np.ndarray:
        """codes -> their strings as a fixed-width numpy array ('<U', or 'S'
        for bytes) of the codes' shape: the collect-boundary decode."""
        import pyarrow as pa

        codes = np.asarray(codes)
        kind = np.bytes_ if self.is_bytes else np.str_
        flat = codes.reshape(-1)
        if len(flat) == 0:
            return np.zeros(codes.shape, dtype=kind)
        try:
            picked = self.arrow.take(pa.array(flat.astype(np.int64,
                                                          copy=False)))
        except pa.ArrowIndexError as e:
            raise IndexError(str(e)) from None
        return picked.to_numpy(zero_copy_only=False).astype(kind) \
            .reshape(codes.shape)

    def __array__(self, dtype=None, copy=None):
        kind = np.bytes_ if self.is_bytes else np.str_
        out = self.arrow.to_numpy(zero_copy_only=False).astype(kind)
        return out if dtype is None else out.astype(dtype)

    def tolist(self) -> list:
        return self.arrow.to_pylist()

    def __repr__(self) -> str:
        return f"StringDict({len(self)} entries, {self.nbytes} bytes)"


def is_string_array(src: np.ndarray) -> bool:
    """True for columns that need dictionary encoding: unicode/bytes
    arrays, or object arrays whose every element is a str (the pandas /
    pyarrow to_numpy pivot shape). The object scan is a full pass, but it
    is the SOUND gate — sniffing only the first element would silently
    stringify mixed object columns."""
    if src.dtype.kind in ("U", "S"):
        return True
    if src.dtype.kind == "O":
        return len(src) > 0 and all(isinstance(x, str) for x in src.flat)
    return False


def as_string_dict(values) -> StringDict:
    """A dictionary as a StringDict: a StringDict as it is, a sorted numpy
    '<U'/'S' array as one."""
    if isinstance(values, StringDict):
        return values
    import pyarrow as pa

    values = np.asarray(values)
    if values.dtype.kind == "O":
        values = values.astype(np.str_)
    if len(values) == 0:
        return StringDict(pa.array([], pa.binary() if values.dtype.kind
                                   == "S" else pa.string()))
    return StringDict(pa.array(values))


def encode_array(src: np.ndarray) -> Tuple[np.ndarray, StringDict]:
    """One string column -> (int32 rank codes, sorted dictionary). Object
    arrays (all-str) are taken as strings."""
    import pyarrow as pa

    src = np.asarray(src)
    if src.dtype.kind == "O":
        src = src.astype(np.str_)
    if len(src) == 0:
        return (np.zeros(0, dtype=CODE_DTYPE), as_string_dict(src))
    return encode_arrow([pa.array(src.reshape(-1))])


def _same_dictionary(a, b) -> bool:
    if a is b:
        return True
    if len(a) != len(b) or a.type != b.type:
        return False
    if [x.address if x is not None else None for x in a.buffers()] \
            == [x.address if x is not None else None for x in b.buffers()] \
            and a.offset == b.offset:
        return True
    return a.equals(b)


def _sorted_distinct(values, distinct: bool):
    """(sorted distinct values, rank of each of `values` in them) for one
    arrow array; the ranks come from the sort alone where `values` is
    `distinct` (one dictionary's entries), as its sorted neighbours show."""
    import pyarrow.compute as pc

    if distinct:
        order = pc.sort_indices(values).to_numpy()
        ordered = values.take(order)
        n = len(ordered)
        if n < 2 or not pc.any(pc.equal(ordered.slice(1),
                                        ordered.slice(0, n - 1))).as_py():
            rank = np.empty(n, CODE_DTYPE)
            rank[order] = np.arange(n, dtype=CODE_DTYPE)
            return ordered, rank
    unique = pc.unique(values)
    ordered = unique.take(pc.sort_indices(unique))
    rank = pc.index_in(values, value_set=ordered).to_numpy().astype(
        CODE_DTYPE)
    return ordered, rank


def encode_arrow(chunks: List) -> Tuple[np.ndarray, StringDict]:
    """Arrow string chunks of one column — plain, or dictionary arrays as a
    parquet reader gives them — -> (int32 rank codes over all rows, in
    order; the column's sorted dictionary), built once: each distinct chunk
    dictionary (chunks of one file share theirs) is sorted or merged once,
    never concatenated per row. No nulls (the callers prove it)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    with spans.span("dict_build") as sp:
        encoded = [c if pa.types.is_dictionary(c.type)
                   else pc.dictionary_encode(c) for c in chunks]
        dicts, which = [], []
        for c in encoded:
            for i, seen in enumerate(dicts):
                if _same_dictionary(seen, c.dictionary):
                    which.append(i)
                    break
            else:
                which.append(len(dicts))
                dicts.append(c.dictionary)
        if not dicts:
            return np.zeros(0, CODE_DTYPE), StringDict(pa.array([],
                                                                pa.string()))
        if len(dicts) > 1:
            wide = pa.large_binary() if pa.types.is_binary(dicts[0].type) \
                or pa.types.is_large_binary(dicts[0].type) \
                else pa.large_string()
            dicts = [d.cast(wide) for d in dicts]
        ordered, rank = _sorted_distinct(
            dicts[0] if len(dicts) == 1 else pa.concat_arrays(dicts),
            distinct=len(dicts) == 1)
        starts = np.cumsum([0] + [len(d) for d in dicts])
        codes = np.empty(sum(len(c) for c in encoded), CODE_DTYPE)
        at = 0
        for c, w in zip(encoded, which):
            idx = c.indices.to_numpy(zero_copy_only=False)
            codes[at:at + len(c)] = rank[starts[w]:starts[w + 1]][idx]
            at += len(c)
        sp.nbytes = ordered.nbytes
    return codes, StringDict(ordered)


def encode_string_columns(
    columns: Dict[str, np.ndarray],
    dicts: Optional[Dict[str, StringDict]] = None,
) -> Tuple[Dict[str, np.ndarray], Optional[Dict[str, StringDict]]]:
    """Replace every string column with its int32 code column; returns
    (columns, dicts) where dicts maps encoded names to their sorted
    dictionaries (merged over any caller-provided pre-encoded `dicts` —
    the parquet/stream paths encode upstream and pass codes through).
    Idempotent for already-encoded columns (int32 codes pass straight
    through). With dense_dict_enabled=False, a string column raises the
    crisp VegaError the callers' fallback contract expects (dense_from_*
    degrade to the host tier on it)."""
    out_dicts: Dict[str, StringDict] = dict(dicts or {})
    out: Dict[str, np.ndarray] = {}
    enabled: Optional[bool] = None  # read the knob once, only if needed
    for name, col in columns.items():
        src = np.asarray(col)
        if not is_string_array(src):
            out[name] = col
            continue
        if enabled is None:
            enabled = dict_enabled()
        if not enabled:
            from vega_tpu.errors import VegaError

            raise VegaError(
                f"column {name!r} holds strings and dense_dict_enabled is "
                "off — string columns have no device form without "
                "dictionary encoding; use the host tier for this data"
            )
        codes, values = encode_array(src)
        out[name] = codes
        out_dicts[name] = values
    return out, (out_dicts or None)


# Merges by the identity of the two dictionaries: a frame recompiles every
# action and builds a new unification each time, over the same dictionaries
# of the same cached sources. Bounded; an entry holds its inputs weakly.
_MERGES: "OrderedDict[tuple, tuple]" = OrderedDict()
_MERGES_MAX = 16
_merges_lock = threading.Lock()


def merge_dicts(left, right) -> Tuple[StringDict, np.ndarray, np.ndarray]:
    """Merge two sorted dictionaries into one sorted dictionary plus the
    per-side remap tables: merged[left_map[c]] == left[c] (resp. right).
    The remap is monotonic — both inputs and the merge are sorted — so
    remapped codes keep rank order and a key-sorted block stays
    key-sorted through the remap. Runs once per pair of dictionaries:
    a second call with the same two objects returns the first's tables."""
    import weakref

    import pyarrow as pa
    import pyarrow.compute as pc

    left, right = as_string_dict(left), as_string_dict(right)
    key = (id(left), id(right))
    with _merges_lock:
        hit = _MERGES.get(key)
        if hit is not None and hit[0]() is left and hit[1]() is right:
            _MERGES.move_to_end(key)
            return hit[2]
    la, ra = left.arrow, right.arrow
    if la.type != ra.type or la.nbytes + ra.nbytes >= 1 << 31:
        wide = pa.large_binary() if left.is_bytes else pa.large_string()
        la, ra = la.cast(wide), ra.cast(wide)
    both = pa.concat_arrays([la, ra])
    unique = pc.unique(both)
    merged = unique.take(pc.sort_indices(unique))
    maps = pc.index_in(both, value_set=merged).to_numpy().astype(CODE_DTYPE)
    out = (StringDict(merged), maps[:len(la)], maps[len(la):])
    with _merges_lock:
        _MERGES[key] = (weakref.ref(left), weakref.ref(right), out)
        while len(_MERGES) > _MERGES_MAX:
            _MERGES.popitem(last=False)
    return out


def lookup_codes(values, strings) -> np.ndarray:
    """The codes of `strings`, every one of which is in the dictionary
    `values`."""
    import pyarrow as pa
    import pyarrow.compute as pc

    values = as_string_dict(values)
    strings = np.asarray(strings)
    if len(strings) == 0:
        return np.zeros(0, CODE_DTYPE)
    probe = pa.array(strings.astype(np.bytes_ if values.is_bytes
                                    else np.str_)).cast(values.arrow.type)
    return pc.index_in(probe, value_set=values.arrow).to_numpy().astype(
        CODE_DTYPE)


def decode_codes(codes: np.ndarray, values) -> np.ndarray:
    """codes -> strings via the dictionary (the collect-boundary decode;
    `values` a StringDict, or a sorted numpy string array). Out-of-range
    codes are a programming error upstream — indexing raises rather than
    papering over them."""
    codes = np.asarray(codes)
    if isinstance(values, StringDict):
        return values.take(codes)
    if len(values) == 0 and len(codes) == 0:
        return np.zeros(0, dtype=values.dtype)
    return values[codes]
