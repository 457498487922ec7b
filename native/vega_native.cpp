// vega_tpu native runtime: the host-tier shuffle hot loops in C++.
//
// The reference implements its entire runtime in native code (Rust); the
// performance-critical pieces for the host tier are the map-side combine
// loop (reference: src/dependency.rs:164-229 — per-element hash + bucket +
// upsert) and the shuffle bucket serialization (bincode there). This module
// implements both for the dominant numeric case:
//
//   bucket_reduce_pairs : hash-bucket + combine (i64 keys, i64|f64 values)
//                         in one pass over a Python sequence of pairs
//   bucket_pairs        : hash-bucket without combine (group_by path)
//   merge_encoded       : reduce-side merge of encoded buckets
//                         (reference: src/rdd/shuffled_rdd.rs:149-170)
//   encode/decode_pairs : compact wire codec for packed rows — replaces
//                         pickle for numeric shuffle buckets
//   hash_i64            : splitmix64 over a raw int64 buffer, bit-identical
//                         to vega_tpu.partitioner.splitmix64 (parity oracle)
//   rows_from_columns   : the dense tier's collect() pivot — Python row
//                         tuples straight from decoded numpy columns, each
//                         untracked by the cyclic collector as it is made
//
// Integer values accumulate in int64 (exact); if accumulation overflows
// int64 the whole call REJECTS (returns None) and the caller redoes the
// work on the pure-Python path, whose bignums are exact — silently
// demoting to double would round integer results, and the two host paths
// must agree bit-for-bit whichever one ran. Wire rows are 16 bytes: i64
// key + 8 value bytes holding either an f64 or an i64, selected by the
// bucket set's is_int flag.
//
// Built as a CPython extension (no pybind11 dependency); loaded lazily by
// vega_tpu/native.py; every caller has a pure-Python fallback (including a
// struct-based decoder for these frames), so absence of a compiler degrades
// performance, not correctness.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstring>
#include <new>
#include <unordered_map>
#include <vector>

namespace {

constexpr uint64_t kMask = 0xFFFFFFFFFFFFFFFFull;

static inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ull;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ull;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBull;
  return x ^ (x >> 31);
}

enum Op : int { OP_ADD = 0, OP_MIN = 1, OP_MAX = 2, OP_PROD = 3 };

static inline double apply_op_d(int op, double a, double b) {
  switch (op) {
    case OP_ADD: return a + b;
    case OP_MIN: return a < b ? a : b;
    case OP_MAX: return a > b ? a : b;
    case OP_PROD: return a * b;
  }
  return a;
}

// Int combine with overflow detection; returns false on overflow.
static inline bool apply_op_i(int op, int64_t a, int64_t b, int64_t* out) {
  switch (op) {
    case OP_ADD: return !__builtin_add_overflow(a, b, out);
    case OP_MIN: *out = a < b ? a : b; return true;
    case OP_MAX: *out = a > b ? a : b; return true;
    case OP_PROD: return !__builtin_mul_overflow(a, b, out);
  }
  *out = a;
  return true;
}

// Dual accumulator: doubles always, int64 exactly while it stays exact.
struct Acc {
  double d;
  int64_t i;
};

struct Row {
  int64_t key;
  int64_t bits;  // f64 or i64 payload, per the frame's is_int flag
};

static inline int64_t d2bits(double d) {
  int64_t b;
  std::memcpy(&b, &d, 8);
  return b;
}

static inline double bits2d(int64_t b) {
  double d;
  std::memcpy(&d, &b, 8);
  return d;
}

// ---- helpers ---------------------------------------------------------------

// Extract (i64 key, value) from one pair. Returns false when the pair is not
// numeric (caller falls back to Python; a pending Python error means a real
// failure).
static inline bool extract_pair(PyObject* item, int64_t* key, double* d,
                                int64_t* i, bool* value_is_int) {
  if (!PyTuple_Check(item) || PyTuple_GET_SIZE(item) != 2) return false;
  PyObject* k = PyTuple_GET_ITEM(item, 0);
  PyObject* v = PyTuple_GET_ITEM(item, 1);
  if (!PyLong_CheckExact(k)) return false;
  int overflow = 0;
  *key = PyLong_AsLongLongAndOverflow(k, &overflow);
  if (overflow != 0) return false;
  if (PyFloat_CheckExact(v)) {
    *d = PyFloat_AS_DOUBLE(v);
    *i = 0;
    *value_is_int = false;
    return true;
  }
  if (PyLong_CheckExact(v)) {
    int64_t lv = PyLong_AsLongLongAndOverflow(v, &overflow);
    if (overflow != 0) return false;
    *d = static_cast<double>(lv);
    *i = lv;
    *value_is_int = true;
    return true;
  }
  return false;
}

static PyObject* rows_to_bytes(const std::vector<Row>& rows) {
  PyObject* out = PyBytes_FromStringAndSize(
      nullptr, static_cast<Py_ssize_t>(rows.size() * sizeof(Row)));
  if (out == nullptr) return nullptr;
  std::memcpy(PyBytes_AS_STRING(out), rows.data(), rows.size() * sizeof(Row));
  return out;
}

// Allocate an uninitialized row blob and expose its write cursor: bucket
// serializers fill rows in place — one copy per bucket instead of a
// staging vector plus memcpy.
static inline PyObject* alloc_row_blob(size_t count, Row** dst) {
  PyObject* blob = PyBytes_FromStringAndSize(
      nullptr, static_cast<Py_ssize_t>(count * sizeof(Row)));
  if (blob != nullptr) *dst = reinterpret_cast<Row*>(PyBytes_AS_STRING(blob));
  return blob;
}

static PyObject* pair_list_from_accs(
    const std::unordered_map<int64_t, Acc>& combined, bool as_int) {
  PyObject* out = PyList_New(static_cast<Py_ssize_t>(combined.size()));
  if (out == nullptr) return nullptr;
  Py_ssize_t idx = 0;
  for (const auto& kv : combined) {
    PyObject* key = PyLong_FromLongLong(kv.first);
    PyObject* value = as_int ? PyLong_FromLongLong(kv.second.i)
                             : PyFloat_FromDouble(kv.second.d);
    if (key == nullptr || value == nullptr) {
      Py_XDECREF(key);
      Py_XDECREF(value);
      Py_DECREF(out);
      return nullptr;
    }
    PyObject* pair = PyTuple_Pack(2, key, value);
    Py_DECREF(key);
    Py_DECREF(value);
    if (pair == nullptr) { Py_DECREF(out); return nullptr; }
    PyList_SET_ITEM(out, idx++, pair);
  }
  return out;
}


// Value-kind homogeneity tracker: the wire format types a whole bucket set
// as int OR float. A partition mixing int and float values must fall back
// to the pickle path to preserve per-value types (group_by returns the
// values themselves). kind: 0=unset, 1=int, 2=float; returns false on mix.
static inline bool track_kind(int* kind, bool value_is_int) {
  int k = value_is_int ? 1 : 2;
  if (*kind == 0) { *kind = k; return true; }
  return *kind == k;
}

// ---- module functions ------------------------------------------------------

// bucket_reduce_pairs(iterable, n_buckets, op) -> (list[bytes], is_int) | None
static PyObject* bucket_reduce_pairs(PyObject*, PyObject* args) {
  PyObject* iterable;
  Py_ssize_t n_buckets;
  int op;
  if (!PyArg_ParseTuple(args, "Oni", &iterable, &n_buckets, &op)) return nullptr;
  if (n_buckets <= 0) {
    PyErr_SetString(PyExc_ValueError, "n_buckets must be positive");
    return nullptr;
  }

  std::vector<std::unordered_map<int64_t, Acc>> buckets(
      static_cast<size_t>(n_buckets));
  PyObject* iter = PyObject_GetIter(iterable);
  if (iter == nullptr) return nullptr;

  int kind = 0;  // value-kind homogeneity (track_kind)
  PyObject* item;
  while ((item = PyIter_Next(iter)) != nullptr) {
    int64_t key;
    double dv;
    int64_t iv;
    bool value_is_int;
    if (!extract_pair(item, &key, &dv, &iv, &value_is_int) ||
        !track_kind(&kind, value_is_int)) {
      Py_DECREF(item);
      Py_DECREF(iter);
      if (PyErr_Occurred()) return nullptr;
      Py_RETURN_NONE;  // non-numeric or mixed int/float -> Python path
    }
    Py_DECREF(item);
    uint64_t h = splitmix64(static_cast<uint64_t>(key) & kMask);
    auto& bucket = buckets[h % static_cast<uint64_t>(n_buckets)];
    auto it = bucket.find(key);
    if (it == bucket.end()) {
      bucket.emplace(key, Acc{dv, iv});
    } else {
      it->second.d = apply_op_d(op, it->second.d, dv);
      if (!apply_op_i(op, it->second.i, iv, &it->second.i)) {
        // Integer accumulation overflowed int64: double semantics would
        // silently round, so reject NOW — every continuation from this
        // state ends in None (all-int -> overflow rejection; a later
        // float -> mixed-type rejection), and the Python redo starts
        // from the source iterator anyway. (item was released above.)
        Py_DECREF(iter);
        Py_RETURN_NONE;
      }
    }
  }
  Py_DECREF(iter);
  if (PyErr_Occurred()) return nullptr;
  const bool all_int = (kind != 2);

  PyObject* result = PyList_New(n_buckets);
  if (result == nullptr) return nullptr;
  for (Py_ssize_t b = 0; b < n_buckets; ++b) {
    Row* dst;
    PyObject* blob = alloc_row_blob(buckets[b].size(), &dst);
    if (blob == nullptr) { Py_DECREF(result); return nullptr; }
    for (const auto& kv : buckets[b]) {
      *dst++ = {kv.first, all_int ? kv.second.i : d2bits(kv.second.d)};
    }
    PyList_SET_ITEM(result, b, blob);
  }
  PyObject* out = Py_BuildValue("(Oi)", result, all_int ? 1 : 0);
  Py_DECREF(result);
  return out;
}

// bucket_pairs(iterable, n_buckets) -> (list[bytes], is_int) | None
static PyObject* bucket_pairs(PyObject*, PyObject* args) {
  PyObject* iterable;
  Py_ssize_t n_buckets;
  if (!PyArg_ParseTuple(args, "On", &iterable, &n_buckets)) return nullptr;
  if (n_buckets <= 0) {
    PyErr_SetString(PyExc_ValueError, "n_buckets must be positive");
    return nullptr;
  }
  std::vector<std::vector<Acc>> vals(static_cast<size_t>(n_buckets));
  std::vector<std::vector<int64_t>> keys(static_cast<size_t>(n_buckets));
  PyObject* iter = PyObject_GetIter(iterable);
  if (iter == nullptr) return nullptr;
  int kind = 0;  // homogeneity: all_int == (kind != 2) after the loop
  PyObject* item;
  while ((item = PyIter_Next(iter)) != nullptr) {
    int64_t key;
    double dv;
    int64_t iv;
    bool value_is_int;
    if (!extract_pair(item, &key, &dv, &iv, &value_is_int) ||
        !track_kind(&kind, value_is_int)) {
      Py_DECREF(item);
      Py_DECREF(iter);
      if (PyErr_Occurred()) return nullptr;
      Py_RETURN_NONE;  // non-numeric or mixed int/float -> Python path
    }
    Py_DECREF(item);
    uint64_t h = splitmix64(static_cast<uint64_t>(key) & kMask);
    size_t b = h % static_cast<uint64_t>(n_buckets);
    keys[b].push_back(key);
    vals[b].push_back({dv, iv});
  }
  Py_DECREF(iter);
  if (PyErr_Occurred()) return nullptr;
  const bool all_int = (kind != 2);

  PyObject* result = PyList_New(n_buckets);
  if (result == nullptr) return nullptr;
  for (Py_ssize_t b = 0; b < n_buckets; ++b) {
    Row* dst;
    PyObject* blob = alloc_row_blob(keys[b].size(), &dst);
    if (blob == nullptr) { Py_DECREF(result); return nullptr; }
    for (size_t r = 0; r < keys[b].size(); ++r) {
      *dst++ = {keys[b][r],
                all_int ? vals[b][r].i : d2bits(vals[b][r].d)};
    }
    PyList_SET_ITEM(result, b, blob);
  }
  PyObject* out = Py_BuildValue("(Oi)", result, all_int ? 1 : 0);
  Py_DECREF(result);
  return out;
}

// Reduce-side merge accumulator. Shared by the one-shot merge_encoded and
// the streaming merge_state_* entry points (shuffle/fetcher.py's pipelined
// fetch feeds buckets here AS THEY ARRIVE, so the merge overlaps network
// time instead of following it). Semantics are identical either way: the
// result is int-typed iff every fed blob was int-typed, and an int64
// combine overflow poisons the state — finish then reports failure and the
// caller redoes the merge with exact Python bignums.
struct MergeState {
  std::unordered_map<int64_t, Acc> combined;
  bool int_inputs = true;   // every blob int-typed so far
  bool overflowed = false;  // an int64 combine overflowed
};

static void merge_state_feed_rows(MergeState* st, const Row* rows,
                                  size_t count, int blob_is_int, int op) {
  st->int_inputs = st->int_inputs && (blob_is_int != 0);
  for (size_t r = 0; r < count; ++r) {
    double dv = blob_is_int ? static_cast<double>(rows[r].bits)
                            : bits2d(rows[r].bits);
    int64_t iv = blob_is_int ? rows[r].bits : 0;
    auto it = st->combined.find(rows[r].key);
    if (it == st->combined.end()) {
      st->combined.emplace(rows[r].key, Acc{dv, iv});
    } else {
      it->second.d = apply_op_d(op, it->second.d, dv);
      if (st->int_inputs && !st->overflowed &&
          !apply_op_i(op, it->second.i, iv, &it->second.i)) {
        st->overflowed = true;
      }
    }
  }
}

static PyObject* merge_state_result(const MergeState& st) {
  if (st.int_inputs && st.overflowed) {
    Py_RETURN_NONE;  // exact Python bignum merge instead of rounding
  }
  return pair_list_from_accs(st.combined, st.int_inputs && !st.overflowed);
}

// merge_encoded(list[(bytes, is_int)], op) -> list[(int, float|int)] | None
static PyObject* merge_encoded(PyObject*, PyObject* args) {
  PyObject* blobs;
  int op;
  if (!PyArg_ParseTuple(args, "Oi", &blobs, &op)) return nullptr;
  PyObject* seq = PySequence_Fast(blobs, "expected a sequence of (bytes, int)");
  if (seq == nullptr) return nullptr;

  MergeState st;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  for (Py_ssize_t idx = 0; idx < n; ++idx) {
    PyObject* entry = PySequence_Fast_GET_ITEM(seq, idx);
    PyObject* blob;
    int blob_is_int;
    if (!PyArg_ParseTuple(entry, "Oi", &blob, &blob_is_int)) {
      Py_DECREF(seq);
      return nullptr;
    }
    char* data;
    Py_ssize_t size;
    if (PyBytes_AsStringAndSize(blob, &data, &size) < 0) {
      Py_DECREF(seq);
      return nullptr;
    }
    merge_state_feed_rows(&st, reinterpret_cast<const Row*>(data),
                          static_cast<size_t>(size) / sizeof(Row),
                          blob_is_int, op);
  }
  Py_DECREF(seq);
  return merge_state_result(st);
}

// ---- streaming merge (accumulator reuse across arriving buckets) ----------

static constexpr const char* kMergeStateCapsule = "vega_tpu.MergeState";

static void merge_state_destroy(PyObject* capsule) {
  delete static_cast<MergeState*>(
      PyCapsule_GetPointer(capsule, kMergeStateCapsule));
}

static MergeState* merge_state_from(PyObject* capsule) {
  return static_cast<MergeState*>(
      PyCapsule_GetPointer(capsule, kMergeStateCapsule));
}

// merge_state_new() -> capsule
static PyObject* merge_state_new(PyObject*, PyObject*) {
  MergeState* st = new (std::nothrow) MergeState();
  if (st == nullptr) return PyErr_NoMemory();
  PyObject* cap = PyCapsule_New(st, kMergeStateCapsule, merge_state_destroy);
  if (cap == nullptr) delete st;
  return cap;
}

// merge_state_feed(capsule, bytes, is_int, op) -> None
// Feeds one encoded bucket into the accumulator. Accepts any buffer
// (bytes or a memoryview over the wire payload) without copying.
static PyObject* merge_state_feed(PyObject*, PyObject* args) {
  PyObject* capsule;
  Py_buffer view;
  int is_int;
  int op;
  if (!PyArg_ParseTuple(args, "Oy*ii", &capsule, &view, &is_int, &op))
    return nullptr;
  MergeState* st = merge_state_from(capsule);
  if (st == nullptr) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  merge_state_feed_rows(st, static_cast<const Row*>(view.buf),
                        static_cast<size_t>(view.len) / sizeof(Row),
                        is_int, op);
  PyBuffer_Release(&view);
  Py_RETURN_NONE;
}

// merge_state_finish(capsule) -> list[(int, float|int)] | None
// None = an int64 combine overflowed somewhere in the stream; the caller
// must redo the whole merge on the exact pure-Python path (the state keeps
// no raw buckets, so the redo refetches — rare by construction).
static PyObject* merge_state_finish(PyObject*, PyObject* args) {
  PyObject* capsule;
  if (!PyArg_ParseTuple(args, "O", &capsule)) return nullptr;
  MergeState* st = merge_state_from(capsule);
  if (st == nullptr) return nullptr;
  return merge_state_result(*st);
}

// decode_pairs(bytes, is_int) -> list[(int, float|int)]
static PyObject* decode_pairs(PyObject*, PyObject* args) {
  PyObject* blob;
  int is_int;
  if (!PyArg_ParseTuple(args, "Op", &blob, &is_int)) return nullptr;
  char* data;
  Py_ssize_t size;
  if (PyBytes_AsStringAndSize(blob, &data, &size) < 0) return nullptr;
  size_t count = static_cast<size_t>(size) / sizeof(Row);
  const Row* rows = reinterpret_cast<const Row*>(data);
  PyObject* out = PyList_New(static_cast<Py_ssize_t>(count));
  if (out == nullptr) return nullptr;
  for (size_t r = 0; r < count; ++r) {
    PyObject* key = PyLong_FromLongLong(rows[r].key);
    PyObject* value = is_int ? PyLong_FromLongLong(rows[r].bits)
                             : PyFloat_FromDouble(bits2d(rows[r].bits));
    if (key == nullptr || value == nullptr) {
      Py_XDECREF(key);
      Py_XDECREF(value);
      Py_DECREF(out);
      return nullptr;
    }
    PyObject* pair = PyTuple_Pack(2, key, value);
    Py_DECREF(key);
    Py_DECREF(value);
    if (pair == nullptr) { Py_DECREF(out); return nullptr; }
    PyList_SET_ITEM(out, static_cast<Py_ssize_t>(r), pair);
  }
  return out;
}

// encode_pairs(iterable) -> (bytes, is_int) | None
static PyObject* encode_pairs(PyObject*, PyObject* args) {
  PyObject* iterable;
  if (!PyArg_ParseTuple(args, "O", &iterable)) return nullptr;
  PyObject* iter = PyObject_GetIter(iterable);
  if (iter == nullptr) return nullptr;
  std::vector<int64_t> ks;
  std::vector<Acc> vs;
  int kind = 0;  // homogeneity: all_int == (kind != 2) after the loop
  PyObject* item;
  while ((item = PyIter_Next(iter)) != nullptr) {
    int64_t key;
    double dv;
    int64_t iv;
    bool value_is_int;
    if (!extract_pair(item, &key, &dv, &iv, &value_is_int) ||
        !track_kind(&kind, value_is_int)) {
      Py_DECREF(item);
      Py_DECREF(iter);
      if (PyErr_Occurred()) return nullptr;
      Py_RETURN_NONE;  // non-numeric or mixed int/float -> Python path
    }
    Py_DECREF(item);
    ks.push_back(key);
    vs.push_back({dv, iv});
  }
  Py_DECREF(iter);
  if (PyErr_Occurred()) return nullptr;
  const bool all_int = (kind != 2);
  std::vector<Row> rows;
  rows.reserve(ks.size());
  for (size_t r = 0; r < ks.size(); ++r) {
    rows.push_back({ks[r], all_int ? vs[r].i : d2bits(vs[r].d)});
  }
  PyObject* blob = rows_to_bytes(rows);
  if (blob == nullptr) return nullptr;
  PyObject* out = Py_BuildValue("(Oi)", blob, all_int ? 1 : 0);
  Py_DECREF(blob);
  return out;
}

// hash_i64(buffer, n_buckets) -> bytes (int64 bucket ids, same length)
static PyObject* hash_i64(PyObject*, PyObject* args) {
  Py_buffer view;
  Py_ssize_t n_buckets;
  if (!PyArg_ParseTuple(args, "y*n", &view, &n_buckets)) return nullptr;
  if (n_buckets <= 0 || view.len % 8 != 0) {
    PyBuffer_Release(&view);
    PyErr_SetString(PyExc_ValueError, "need int64 buffer and n_buckets > 0");
    return nullptr;
  }
  size_t n = static_cast<size_t>(view.len) / 8;
  PyObject* out = PyBytes_FromStringAndSize(nullptr, view.len);
  if (out == nullptr) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  const int64_t* keys = static_cast<const int64_t*>(view.buf);
  int64_t* dst = reinterpret_cast<int64_t*>(PyBytes_AS_STRING(out));
  for (size_t i = 0; i < n; ++i) {
    dst[i] = static_cast<int64_t>(
        splitmix64(static_cast<uint64_t>(keys[i])) %
        static_cast<uint64_t>(n_buckets));
  }
  PyBuffer_Release(&view);
  return out;
}

// ---- collect() pivot: columns -> row tuples --------------------------------

// One element of a column as the Python object ndarray.tolist() makes of it
// (new reference, nullptr with the error set).
typedef PyObject* (*ToPy)(const char*);

template <typename T>
static PyObject* signed_to_py(const char* p) {
  T x;
  std::memcpy(&x, p, sizeof(T));
  return PyLong_FromLongLong(static_cast<long long>(x));
}

template <typename T>
static PyObject* unsigned_to_py(const char* p) {
  T x;
  std::memcpy(&x, p, sizeof(T));
  return PyLong_FromUnsignedLongLong(static_cast<unsigned long long>(x));
}

template <typename T>
static PyObject* float_to_py(const char* p) {
  T x;
  std::memcpy(&x, p, sizeof(T));
  return PyFloat_FromDouble(static_cast<double>(x));
}

static PyObject* bool_to_py(const char* p) {
  return Py_NewRef(*p ? Py_True : Py_False);
}

// The converter of a buffer's struct-module format (numpy's dtype char,
// after an optional native byte-order prefix) at its itemsize, or nullptr
// for every dtype this builder does not make rows of.
static ToPy converter_of(const char* format, Py_ssize_t itemsize) {
  if (format == nullptr) format = "B";
  const bool little = PY_LITTLE_ENDIAN != 0;
  if (*format == '@' || *format == '=' || (*format == '<' && little) ||
      (*format == '>' && !little)) {
    ++format;
  }
  if (format[0] == '\0' || format[1] != '\0') return nullptr;
  switch (format[0]) {
    case 'b': case 'h': case 'i': case 'l': case 'q':
      switch (itemsize) {
        case 1: return signed_to_py<int8_t>;
        case 2: return signed_to_py<int16_t>;
        case 4: return signed_to_py<int32_t>;
        case 8: return signed_to_py<int64_t>;
      }
      return nullptr;
    case 'B': case 'H': case 'I': case 'L': case 'Q':
      switch (itemsize) {
        case 1: return unsigned_to_py<uint8_t>;
        case 2: return unsigned_to_py<uint16_t>;
        case 4: return unsigned_to_py<uint32_t>;
        case 8: return unsigned_to_py<uint64_t>;
      }
      return nullptr;
    case 'f':
      return itemsize == 4 ? float_to_py<float> : nullptr;
    case 'd':
      return itemsize == 8 ? float_to_py<double> : nullptr;
    case '?':
      return itemsize == 1 ? bool_to_py : nullptr;
  }
  return nullptr;
}

struct Column {
  Py_buffer view;
  ToPy to_py;
  const char* data;
  Py_ssize_t itemsize;
};

static void release_columns(std::vector<Column>* cols) {
  for (Column& c : *cols) PyBuffer_Release(&c.view);
}

// A tuple of `count` elements of row `r`, or nullptr with the error set.
// It holds ints, floats, bools or such tuples, so it cannot be in a cycle:
// it leaves the collector's lists now, as the collector's first pass over
// it would (_PyTuple_MaybeUntrack), and no pass ever walks it.
static PyObject* row_tuple(const Column* cols, Py_ssize_t count,
                           Py_ssize_t r) {
  PyObject* tup = PyTuple_New(count);
  if (tup == nullptr) return nullptr;
  for (Py_ssize_t c = 0; c < count; ++c) {
    PyObject* item = cols[c].to_py(cols[c].data + r * cols[c].itemsize);
    if (item == nullptr) {
      Py_DECREF(tup);
      return nullptr;
    }
    PyTuple_SET_ITEM(tup, c, item);
  }
  PyObject_GC_UnTrack(tup);
  return tup;
}

// rows_from_columns(columns, nest) -> list
// columns: 1-D C-contiguous numpy arrays of one length, of a bool, integer,
// float32 or float64 dtype in native byte order (TypeError for any other:
// the caller keeps its tolist() path for those). Row r is the tuple of the
// columns' elements, or with nest (two columns or more) (c0[r], (c1[r],
// ...)), the dense join's (k, (lv, rv)); each element the object
// ndarray.tolist() makes of it. Holds the GIL throughout: it makes Python
// objects.
static PyObject* rows_from_columns(PyObject*, PyObject* args) {
  PyObject* columns;
  int nest;
  if (!PyArg_ParseTuple(args, "Op", &columns, &nest)) return nullptr;
  PyObject* seq = PySequence_Fast(columns, "expected a sequence of arrays");
  if (seq == nullptr) return nullptr;
  const Py_ssize_t n_cols = PySequence_Fast_GET_SIZE(seq);
  if (nest && n_cols < 2) {
    Py_DECREF(seq);
    PyErr_SetString(PyExc_ValueError, "nest needs two columns or more");
    return nullptr;
  }
  std::vector<Column> cols;
  cols.reserve(static_cast<size_t>(n_cols));
  Py_ssize_t n = 0;
  for (Py_ssize_t c = 0; c < n_cols; ++c) {
    cols.emplace_back();
    Column& last = cols.back();
    if (PyObject_GetBuffer(PySequence_Fast_GET_ITEM(seq, c), &last.view,
                           PyBUF_STRIDES | PyBUF_FORMAT) < 0) {
      // numpy exports no buffer of a datetime column, for one: a dtype
      // this builder does not take
      cols.pop_back();
      PyErr_Clear();
      release_columns(&cols);
      Py_DECREF(seq);
      PyErr_SetString(PyExc_TypeError, "column exports no buffer");
      return nullptr;
    }
    last.to_py = converter_of(last.view.format, last.view.itemsize);
    last.data = static_cast<const char*>(last.view.buf);
    last.itemsize = last.view.itemsize;
    const bool flat = last.view.ndim == 1 &&
        (last.view.strides == nullptr ||
         last.view.strides[0] == last.view.itemsize);
    if (last.to_py == nullptr || !flat) {
      PyErr_Format(PyExc_TypeError,
                   "column %zd: no rows of format '%s'%s", c,
                   last.view.format ? last.view.format : "B",
                   flat ? "" : ", not a contiguous 1-D column");
      release_columns(&cols);
      Py_DECREF(seq);
      return nullptr;
    }
    const Py_ssize_t len = last.view.shape[0];
    if (c > 0 && len != n) {
      release_columns(&cols);
      Py_DECREF(seq);
      PyErr_SetString(PyExc_ValueError, "columns differ in length");
      return nullptr;
    }
    n = len;
  }
  Py_DECREF(seq);

  PyObject* out = PyList_New(n);
  if (out == nullptr) {
    release_columns(&cols);
    return nullptr;
  }
  const Column* cs = cols.data();
  for (Py_ssize_t r = 0; r < n; ++r) {
    PyObject* row;
    if (nest) {
      PyObject* head = cs[0].to_py(cs[0].data + r * cs[0].itemsize);
      PyObject* tail = head == nullptr ? nullptr
                                       : row_tuple(cs + 1, n_cols - 1, r);
      row = tail == nullptr ? nullptr : PyTuple_New(2);
      if (row == nullptr) {
        Py_XDECREF(head);
        Py_XDECREF(tail);
      } else {
        PyTuple_SET_ITEM(row, 0, head);
        PyTuple_SET_ITEM(row, 1, tail);
        PyObject_GC_UnTrack(row);
      }
    } else {
      row = row_tuple(cs, n_cols, r);
    }
    if (row == nullptr) {
      Py_DECREF(out);  // its unfilled slots are NULL, which it skips
      release_columns(&cols);
      return nullptr;
    }
    PyList_SET_ITEM(out, r, row);
  }
  release_columns(&cols);
  return out;
}

static PyMethodDef kMethods[] = {
    {"bucket_reduce_pairs", bucket_reduce_pairs, METH_VARARGS,
     "One-pass hash-bucket + combine over (int, number) pairs."},
    {"bucket_pairs", bucket_pairs, METH_VARARGS,
     "Hash-bucket (int, number) pairs without combining."},
    {"merge_encoded", merge_encoded, METH_VARARGS,
     "Merge encoded (bytes, is_int) buckets with a named op."},
    {"merge_state_new", merge_state_new, METH_NOARGS,
     "New streaming-merge accumulator (capsule)."},
    {"merge_state_feed", merge_state_feed, METH_VARARGS,
     "Feed one encoded bucket into a streaming-merge accumulator."},
    {"merge_state_finish", merge_state_finish, METH_VARARGS,
     "Finish a streaming merge: pair list, or None on int64 overflow."},
    {"decode_pairs", decode_pairs, METH_VARARGS,
     "Decode packed rows to a list of pairs."},
    {"encode_pairs", encode_pairs, METH_VARARGS,
     "Encode (int, number) pairs to packed rows."},
    {"hash_i64", hash_i64, METH_VARARGS,
     "splitmix64 % n_buckets over an int64 buffer."},
    {"rows_from_columns", rows_from_columns, METH_VARARGS,
     "Row tuples (flat, or (c0, (c1, ...)) with nest) from numpy columns."},
    {nullptr, nullptr, 0, nullptr},
};

static struct PyModuleDef kModule = {
    PyModuleDef_HEAD_INIT, "_vega_native",
    "vega_tpu native shuffle hot loops", -1, kMethods,
};

}  // namespace

PyMODINIT_FUNC PyInit__vega_native(void) { return PyModule_Create(&kModule); }
