// maxmq_decode — CPython extension owning the decode half of the
// fixed-slot match path: candidate verification + the full per-topic
// subscriber union (maxmq_tpu/matching/sig.py:decode_fixed), plus the
// SubscriberSet result type itself.
//
// Why a C extension and not the ctypes runtime (maxmq_native.cpp): the
// decode's output is Python objects — per-topic SubscriberSets holding
// {client_id: Subscription} dicts, the merged-Subscribers shape of the
// reference's TopicsIndex.Subscribers (vendor/github.com/mochi-co/
// mqtt/v2/topics.go:484-518) — so the hot loop IS object construction
// and PyDict traffic. Doing the verify compare, the dict inserts, AND
// the result-object allocation in one C pass removes the interpreter
// dispatch that capped the python walk at ~1.5M pairs/s and the
// ~1.3us/topic object-building tail.
//
// SubscriberSet here is a heap type with C-speed construction; the
// cold-path semantics (merge_subscription, Subscription copying for
// deep_copy) stay in python and are registered via configure() so the
// v5 identifier-union rules live in exactly one place (trie.py:32-57).
//
// Per compiled snapshot the python side flattens every row's entry
// walk into an ACTION STREAM (CSR over rows). Each action is one of:
//   PLAIN  — insert the stored Subscription aliased (the common case);
//            a same-client collision calls merge_subscription exactly
//            like SubscriberSet.add (trie.py)
//   MERGE  — v5 subscription identifiers present: ALWAYS route through
//            merge_subscription so the identifier-union copy semantics
//            are preserved even for the first insert
//   SHARED — shared-group candidate: shared[(group, filter)][cid] = sub
//            [MQTT-4.8.2-4]; pre-built (group, filter) key tuples
// Verification itself mirrors sig.py:verify_pairs (window compare,
// depth rule, '$'-exclusion, valid bit) on the same arrays.
//
// The device-free path (sig.py:SigEngine.subscribers_host_batch) enters
// here once a batch: host_batch tokenizes and probes through the ctypes
// runtime's entry points (by address, on its handles) and then verifies
// and unions in the same call, so no Python runs between the steps.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#if PY_VERSION_HEX < 0x030c0000
// pre-3.12 spelling of the PyMemberDef type/flag constants
#include <structmember.h>
#ifndef Py_T_OBJECT_EX
#define Py_T_OBJECT_EX T_OBJECT_EX
#endif
#endif

#include <algorithm>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <string>
#include <unordered_map>

// write-intent prefetch (the union read-modify-writes its slot);
// low temporal locality — each slot is touched once per union
#if defined(__GNUC__) || defined(__clang__)
#define PREFETCH_W(p) __builtin_prefetch((p), 1, 1)
#define PREFETCH_R(p) __builtin_prefetch((p), 0, 1)
#else
#define PREFETCH_W(p) ((void)0)
#define PREFETCH_R(p) ((void)0)
#endif
#include <vector>

namespace {

constexpr int32_t VER_PLUS = -1;   // '+' — matches any present level
constexpr int32_t VER_ANY = -2;    // past the filter / probe window
constexpr uint8_t FLAG_EXACT = 1;  // no trailing '#': depth must equal
constexpr uint8_t FLAG_WILDF = 2;  // leading wildcard: '$'-excluded
constexpr uint8_t FLAG_VALID = 4;  // row exists in this snapshot

constexpr uint8_t ACT_PLAIN = 0;
constexpr uint8_t ACT_MERGE = 1;
constexpr uint8_t ACT_SHARED = 2;

// registered by trie.py:configure() — the python-side semantics
PyObject *g_merge_fn = nullptr;     // merge_subscription(base, new, filt)
PyObject *g_copy_sub = nullptr;     // copy_subscription(sub)

// ----------------------------------------------------------------- //
//  SubscriberSet — the C result type                                //
// ----------------------------------------------------------------- //

struct SubSetObject {
  PyObject_HEAD
  PyObject *subscriptions;  // dict: client_id -> Subscription
  PyObject *shared;         // dict: (group, filter) -> {cid: Subscription}
};

PyTypeObject *g_subset_type = nullptr;  // set at module init

SubSetObject *subset_alloc() {
  auto *self = PyObject_GC_New(SubSetObject, g_subset_type);
  if (!self) return nullptr;
  self->subscriptions = nullptr;
  self->shared = nullptr;
  PyObject_GC_Track(self);
  return self;
}

// fast constructor used by decode_batch: steals nothing, fills missing
// dicts lazily at first attribute read (see subset_getattro note) —
// no: keep it simple and always materialize, dict alloc is ~40ns
SubSetObject *subset_new_fast(PyObject *subs, PyObject *shared) {
  auto *self = subset_alloc();
  if (!self) return nullptr;
  self->subscriptions = subs ? Py_NewRef(subs) : PyDict_New();
  self->shared = shared ? Py_NewRef(shared) : PyDict_New();
  if (!self->subscriptions || !self->shared) {
    Py_DECREF(self);
    return nullptr;
  }
  return self;
}

int subset_init(PyObject *self_o, PyObject *args, PyObject *kwargs) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  PyObject *subs = nullptr, *shared = nullptr;
  static const char *kwlist[] = {"subscriptions", "shared", nullptr};
  if (!PyArg_ParseTupleAndKeywords(args, kwargs, "|OO",
                                   const_cast<char **>(kwlist), &subs,
                                   &shared))
    return -1;
  if (subs == Py_None) subs = nullptr;
  if (shared == Py_None) shared = nullptr;
  PyObject *ns = subs ? Py_NewRef(subs) : PyDict_New();
  PyObject *nh = shared ? Py_NewRef(shared) : PyDict_New();
  if (!ns || !nh) {
    Py_XDECREF(ns);
    Py_XDECREF(nh);
    return -1;
  }
  Py_XSETREF(self->subscriptions, ns);
  Py_XSETREF(self->shared, nh);
  return 0;
}

int subset_traverse(PyObject *self_o, visitproc visit, void *arg) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  Py_VISIT(self->subscriptions);
  Py_VISIT(self->shared);
  return 0;
}

int subset_clear(PyObject *self_o) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  Py_CLEAR(self->subscriptions);
  Py_CLEAR(self->shared);
  return 0;
}

void subset_dealloc(PyObject *self_o) {
  PyObject_GC_UnTrack(self_o);
  subset_clear(self_o);
  PyTypeObject *tp = Py_TYPE(self_o);
  PyObject_GC_Del(self_o);
  Py_DECREF(tp);  // heap types own a ref from each instance
}

// add(client_id, sub, filter_) — merge-insert one non-shared
// subscription; mirrors trie.py SubscriberSet.add
PyObject *subset_add(PyObject *self_o, PyObject *const *args,
                     Py_ssize_t nargs) {
  if (nargs != 3) {
    PyErr_SetString(PyExc_TypeError,
                    "add(client_id, sub, filter_) takes 3 arguments");
    return nullptr;
  }
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  PyObject *cur = PyDict_GetItemWithError(self->subscriptions, args[0]);
  if (!cur && PyErr_Occurred()) return nullptr;
  PyObject *mg = PyObject_CallFunctionObjArgs(
      g_merge_fn, cur ? cur : Py_None, args[1], args[2], nullptr);
  if (!mg) return nullptr;
  const int rc = PyDict_SetItem(self->subscriptions, args[0], mg);
  Py_DECREF(mg);
  if (rc < 0) return nullptr;
  Py_RETURN_NONE;
}

// add_shared(group, filter_, client_id, sub)
PyObject *subset_add_shared(PyObject *self_o, PyObject *const *args,
                            Py_ssize_t nargs) {
  if (nargs != 4) {
    PyErr_SetString(
        PyExc_TypeError,
        "add_shared(group, filter_, client_id, sub) takes 4 arguments");
    return nullptr;
  }
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  PyObject *key = PyTuple_Pack(2, args[0], args[1]);
  if (!key) return nullptr;
  PyObject *g = PyDict_GetItemWithError(self->shared, key);
  if (!g) {
    if (PyErr_Occurred()) {
      Py_DECREF(key);
      return nullptr;
    }
    g = PyDict_New();
    if (!g || PyDict_SetItem(self->shared, key, g) < 0) {
      Py_XDECREF(g);
      Py_DECREF(key);
      return nullptr;
    }
    Py_DECREF(g);  // borrowed from self->shared hereafter
  }
  Py_DECREF(key);
  if (PyDict_SetItem(g, args[2], args[3]) < 0) return nullptr;
  Py_RETURN_NONE;
}

// deep_copy() — copies every Subscription via the registered python
// helper; hook-facing cold path (hooks may mutate delivery params)
PyObject *subset_deep_copy(PyObject *self_o, PyObject *) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  PyObject *subs = PyDict_New(), *shared = nullptr;
  if (subs) shared = PyDict_New();
  if (!subs || !shared) {
    Py_XDECREF(subs);
    Py_XDECREF(shared);
    return nullptr;
  }
  auto bail = [&]() -> PyObject * {
    Py_DECREF(subs);
    Py_DECREF(shared);
    return nullptr;
  };
  PyObject *k, *v;
  Py_ssize_t pos = 0;
  while (PyDict_Next(self->subscriptions, &pos, &k, &v)) {
    PyObject *cp = PyObject_CallOneArg(g_copy_sub, v);
    if (!cp || PyDict_SetItem(subs, k, cp) < 0) {
      Py_XDECREF(cp);
      return bail();
    }
    Py_DECREF(cp);
  }
  pos = 0;
  while (PyDict_Next(self->shared, &pos, &k, &v)) {
    PyObject *m = PyDict_New();
    if (!m || PyDict_SetItem(shared, k, m) < 0) {
      Py_XDECREF(m);
      return bail();
    }
    Py_DECREF(m);
    PyObject *k2, *v2;
    Py_ssize_t pos2 = 0;
    while (PyDict_Next(v, &pos2, &k2, &v2)) {
      PyObject *cp = PyObject_CallOneArg(g_copy_sub, v2);
      if (!cp || PyDict_SetItem(m, k2, cp) < 0) {
        Py_XDECREF(cp);
        return bail();
      }
      Py_DECREF(cp);
    }
  }
  auto *out = subset_new_fast(subs, shared);
  Py_DECREF(subs);
  Py_DECREF(shared);
  return reinterpret_cast<PyObject *>(out);
}

// select_copy() — the hook modify-chain form: FRESH outer dicts (the
// hook may add/drop/replace entries anywhere) over ALIASED Subscription
// records (immutable by contract, ADR 009). One C call replaces the
// per-publish python dict copies on the hook-present fan-out path.
PyObject *subset_select_copy(PyObject *self_o, PyObject *) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  PyObject *subs = PyDict_Copy(self->subscriptions);
  if (!subs) return nullptr;
  PyObject *shared = PyDict_New();
  if (!shared) {
    Py_DECREF(subs);
    return nullptr;
  }
  PyObject *k, *v;
  Py_ssize_t pos = 0;
  while (PyDict_Next(self->shared, &pos, &k, &v)) {
    PyObject *m = PyDict_Copy(v);
    if (!m || PyDict_SetItem(shared, k, m) < 0) {
      Py_XDECREF(m);
      Py_DECREF(subs);
      Py_DECREF(shared);
      return nullptr;
    }
    Py_DECREF(m);
  }
  auto *out = subset_new_fast(subs, shared);
  Py_DECREF(subs);
  Py_DECREF(shared);
  return reinterpret_cast<PyObject *>(out);
}

Py_ssize_t subset_len(PyObject *self_o) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  Py_ssize_t n = PyDict_Size(self->subscriptions);
  PyObject *k, *v;
  Py_ssize_t pos = 0;
  while (PyDict_Next(self->shared, &pos, &k, &v)) n += PyDict_Size(v);
  return n;
}

PyObject *subset_richcompare(PyObject *a, PyObject *b, int op) {
  if ((op != Py_EQ && op != Py_NE) ||
      !PyObject_TypeCheck(a, g_subset_type) ||
      !PyObject_TypeCheck(b, g_subset_type))
    Py_RETURN_NOTIMPLEMENTED;
  auto *x = reinterpret_cast<SubSetObject *>(a);
  auto *y = reinterpret_cast<SubSetObject *>(b);
  int eq = PyObject_RichCompareBool(x->subscriptions, y->subscriptions,
                                    Py_EQ);
  if (eq > 0) eq = PyObject_RichCompareBool(x->shared, y->shared, Py_EQ);
  if (eq < 0) return nullptr;
  return PyBool_FromLong(op == Py_EQ ? eq : !eq);
}

PyObject *subset_repr(PyObject *self_o) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  return PyUnicode_FromFormat("SubscriberSet(subscriptions=%R, shared=%R)",
                              self->subscriptions, self->shared);
}

// ----------------------------------------------------------------- //
//  resolve(registry) — a result against the client registry         //
// ----------------------------------------------------------------- //
//
// The fan-out delivers only to clients that have a session, and on a
// large table nearly every matched entry has none. So a result resolves
// itself against the registry's dict in one pass (the contract is
// SubscriberSet.resolve's, matching/trie.py; ADR 007):
//
//   resolve(registry[, kept]) -> (pairs, shared, matched, resolved)
//
// pairs are the (client, sub) of the plain entries whose id is a key of
// the registry, in iteration order; shared is the $share map cut to the
// keys with a registered candidate, member maps aliased whole. Nothing
// is written onto the result: it is cached and shared. kept is the
// registry's memory of its $share counts, (group, filter) -> (members,
// len(members), hits), emptied by its owner when a session comes or
// goes: a key whose entry holds this very map at this length is not
// walked (a row hands out one immutable map, publish after publish).

struct Resolve {
  PyObject *reg;      // borrowed: the registry dict
  PyObject *kept;     // borrowed: the $share counts dict, or nullptr
  PyObject *pairs;    // owned until resolve_finish
  Py_ssize_t matched;
  Py_ssize_t resolved;
};

// args are resolve()'s own: the registry dict, then kept or None
bool resolve_begin(Resolve *r, PyObject *const *args, Py_ssize_t nargs) {
  PyObject *kept = nargs == 2 && args[1] != Py_None ? args[1] : nullptr;
  if (nargs < 1 || nargs > 2 || !PyDict_Check(args[0]) ||
      (kept && !PyDict_Check(kept))) {
    PyErr_SetString(PyExc_TypeError,
                    "resolve() takes the registry dict and, optionally, "
                    "the dict of its kept $share counts");
    return false;
  }
  *r = {args[0], kept, PyList_New(0), 0, 0};
  return r->pairs != nullptr;
}

// one plain entry: a registered client's (client, sub) joins the list
static inline int resolve_entry(Resolve *r, PyObject *cid, PyObject *sub) {
  PyObject *client = PyDict_GetItemWithError(r->reg, cid);  // borrowed
  if (!client) return PyErr_Occurred() ? -1 : 0;
  PyObject *pair = PyTuple_Pack(2, client, sub);
  if (!pair) return -1;
  const int rc = PyList_Append(r->pairs, pair);
  Py_DECREF(pair);
  return rc;
}

// how many ids of one $share member map are keys of the registry: the
// kept count of this very map at this length, else a walk (which then
// is the kept one); -1 with an exception set
static Py_ssize_t resolve_hits(Resolve *r, PyObject *key, PyObject *members) {
  const Py_ssize_t n = PyDict_GET_SIZE(members);
  if (r->kept) {
    PyObject *e = PyDict_GetItemWithError(r->kept, key);  // borrowed
    if (!e && PyErr_Occurred()) return -1;
    if (e && PyTuple_CheckExact(e) && PyTuple_GET_SIZE(e) == 3 &&
        PyTuple_GET_ITEM(e, 0) == members) {
      const Py_ssize_t len = PyLong_AsSsize_t(PyTuple_GET_ITEM(e, 1));
      const Py_ssize_t hits = PyLong_AsSsize_t(PyTuple_GET_ITEM(e, 2));
      if ((len == -1 || hits == -1) && PyErr_Occurred()) return -1;
      if (len == n && hits >= 0) return hits;
    }
  }
  PyObject *cid, *sub;
  Py_ssize_t pos = 0, hits = 0;
  while (PyDict_Next(members, &pos, &cid, &sub)) {
    if (PyDict_GetItemWithError(r->reg, cid))
      hits++;
    else if (PyErr_Occurred())
      return -1;
  }
  if (r->kept) {
    PyObject *e = Py_BuildValue("(Onn)", members, n, hits);
    const int rc = e ? PyDict_SetItem(r->kept, key, e) : -1;
    Py_XDECREF(e);
    if (rc < 0) return -1;
  }
  return hits;
}

// the $share half: NEW reference to the cut map (always a dict)
PyObject *resolve_shared(Resolve *r, PyObject *shared) {
  PyObject *cut = PyDict_New();
  if (!cut || !shared) return cut;
  PyObject *key, *members;
  Py_ssize_t pos = 0;
  while (PyDict_Next(shared, &pos, &key, &members)) {
    const Py_ssize_t hits = resolve_hits(r, key, members);
    if (hits < 0) {
      Py_DECREF(cut);
      return nullptr;
    }
    r->matched += PyDict_GET_SIZE(members);
    r->resolved += hits;
    if (hits && PyDict_SetItem(cut, key, members) < 0) {
      Py_DECREF(cut);
      return nullptr;
    }
  }
  return cut;
}

// ``rc`` is the plain walk's outcome, ``plain`` its entry count; the
// pairs list is consumed either way
PyObject *resolve_finish(Resolve *r, int rc, Py_ssize_t plain,
                         PyObject *shared) {
  PyObject *cut = rc < 0 ? nullptr : resolve_shared(r, shared);
  if (!cut) {
    Py_DECREF(r->pairs);
    return nullptr;
  }
  return Py_BuildValue("(NNnn)", r->pairs, cut, r->matched + plain,
                       r->resolved + PyList_GET_SIZE(r->pairs));
}

PyObject *subset_resolve(PyObject *self_o, PyObject *const *args,
                         Py_ssize_t nargs) {
  auto *self = reinterpret_cast<SubSetObject *>(self_o);
  Resolve r;
  if (!resolve_begin(&r, args, nargs)) return nullptr;
  PyObject *cid, *sub;
  Py_ssize_t pos = 0;
  int rc = 0;
  while (rc == 0 && PyDict_Next(self->subscriptions, &pos, &cid, &sub))
    rc = resolve_entry(&r, cid, sub);
  return resolve_finish(&r, rc, PyDict_GET_SIZE(self->subscriptions),
                        self->shared);
}

PyMemberDef subset_members[] = {
    {"subscriptions", Py_T_OBJECT_EX, offsetof(SubSetObject, subscriptions),
     0, "client_id -> merged Subscription"},
    {"shared", Py_T_OBJECT_EX, offsetof(SubSetObject, shared), 0,
     "(group, filter) -> {client_id: Subscription}"},
    {nullptr, 0, 0, 0, nullptr}};

PyMethodDef subset_methods[] = {
    {"add", reinterpret_cast<PyCFunction>(subset_add), METH_FASTCALL,
     "Merge-insert a non-shared subscription."},
    {"add_shared", reinterpret_cast<PyCFunction>(subset_add_shared),
     METH_FASTCALL, "Insert a shared-group candidate."},
    {"deep_copy", subset_deep_copy, METH_NOARGS,
     "Subscription-deep copy for hooks that may mutate."},
    {"select_copy", subset_select_copy, METH_NOARGS,
     "Fresh outer dicts over aliased records (hook modify-chain form)."},
    {"resolve", reinterpret_cast<PyCFunction>(subset_resolve), METH_FASTCALL,
     "(pairs, shared, matched, resolved) against the registry dict; its "
     "kept $share counts, if given, spare the walk of a known map."},
    {nullptr, nullptr, 0, nullptr}};

PyType_Slot subset_slots[] = {
    {Py_tp_doc, const_cast<char *>(
         "Result of a topic match: per-client merged non-shared "
         "subscriptions and shared-group candidate maps "
         "(group -> client -> subscription). C-accelerated twin of "
         "matching/trie.py's python fallback.")},
    {Py_tp_init, reinterpret_cast<void *>(subset_init)},
    {Py_tp_dealloc, reinterpret_cast<void *>(subset_dealloc)},
    {Py_tp_traverse, reinterpret_cast<void *>(subset_traverse)},
    {Py_tp_clear, reinterpret_cast<void *>(subset_clear)},
    {Py_tp_members, subset_members},
    {Py_tp_methods, subset_methods},
    {Py_sq_length, reinterpret_cast<void *>(subset_len)},
    {Py_tp_richcompare, reinterpret_cast<void *>(subset_richcompare)},
    {Py_tp_repr, reinterpret_cast<void *>(subset_repr)},
    {0, nullptr}};

PyType_Spec subset_spec = {
    "maxmq_decode.SubscriberSet", sizeof(SubSetObject), 0,
    Py_TPFLAGS_DEFAULT | Py_TPFLAGS_BASETYPE | Py_TPFLAGS_HAVE_GC,
    subset_slots};

// ----------------------------------------------------------------- //
//  DeliveryIntents — the fan-out hot-path result type               //
// ----------------------------------------------------------------- //
//
// The broker's fan-out does not need a {client_id: Subscription} dict
// per publish — it needs to ITERATE deliveries (reference boundary:
// publishToSubscribers consuming Subscribers(), vendor/.../v2/
// server.go:766-793). Materializing the merged dict per topic is what
// capped the 1M-sub decode at ~12K topics/s: ~330 scattered dict
// inserts + ~660 refcount writes across a ~1M-object heap per topic
// (BASELINE-COMPARE.md r03). DeliveryIntents replaces that with two
// flat pointer arrays BORROWED from the immutable decode table (kept
// alive by one strong ref to the table capsule): construction per
// row-set is an epoch-stamped dedupe writing int32s and pointers —
// no dict, no per-entry refcounting. Same-client overlapping-filter
// collisions (rare) still route through merge_subscription and own
// their merged record. Shared-group candidates keep the dict shape
// ($share selection needs keyed maps). to_set() materializes a full
// SubscriberSet lazily for the hook path (on_select_subscribers) and
// caches it — intents are cached per row-set and shared across
// topics, so consumers treat them as immutable, like cached sets.
//
// CHAINED form (the cold-stream wall killer): on fan-out-heavy corpora
// one shallow-'#' row carries hundreds of entries and the other rows a
// handful, and a cold unique-topic stream makes every row SET distinct
// — so the per-topic union re-copied those hundreds of pairs through
// the DRAM-latency-bound mark table every single topic (measured
// ~43ns/pair, 14us/topic at 1M subs). A chained intents instead holds
// a strong ref to the fat row's SINGLE-ROW cached intents (immutable,
// built once per table rotation) plus only the thin per-topic tail,
// with same-client collisions against the base expressed as slot
// OVERRIDES applied during iteration. Construction cost per topic
// drops from O(total pairs) to O(tail pairs); iteration still yields
// exactly the merged (client, Subscription) stream.

struct IntentsObject {
  PyObject_HEAD
  PyObject *table_cap;  // strong ref: keeps borrowed cid/sub ptrs alive
  Py_ssize_t n;         // OWN plain (non-shared) delivery entries
  PyObject **cids;      // [n] borrowed from the table's cid list
  PyObject **subs;      // [n] borrowed, or owned when owned[i]
  uint8_t *owned;       // [n] subs[i] is an owned merged Subscription
  PyObject *shared;     // (group, filter) -> {cid: sub}, or NULL
  PyObject *set_cache;  // lazily-built SubscriberSet twin
  // chain: own entries are the tail; base holds the fat row's pairs
  // chain bases (round 5: a LIST — heavy cold sets hold several fat
  // '#' rows whose per-row intents all repeat across topics even
  // though their combinations do not): cached single-row intents in
  // ascending row order. The iteration/override slot space is the
  // concatenation of the bases' entries; base_off[j] is base j's
  // first global slot, base_off[n_bases] the total.
  IntentsObject **bases;  // strong refs; one block with base_off
  int32_t *base_off;      // [n_bases + 1] cumulative entry offsets
  int32_t n_bases;
  int32_t *ovr_slots;   // [n_ovr] base slots shadowed, ascending
  PyObject **ovr_subs;  // [n_ovr] owned merged Subscriptions
  Py_ssize_t n_ovr;
  uint8_t sel_seen;     // select_set() ran once (cache on the re-hit)
};

// total plain entries a consumer sees (tail + bases; overrides shadow)
static inline Py_ssize_t intents_total(const IntentsObject *self) {
  return self->n + (self->n_bases ? self->base_off[self->n_bases] : 0);
}

// resolve a global base slot to the base's stored subscription
static inline PyObject *base_sub_at(const IntentsObject *self,
                                    int32_t gs) {
  int32_t b = 0;
  while (gs >= self->base_off[b + 1]) b++;
  return self->bases[b]->subs[gs - self->base_off[b]];
}

static inline PyObject *base_cid_at(const IntentsObject *self,
                                    int32_t gs) {
  int32_t b = 0;
  while (gs >= self->base_off[b + 1]) b++;
  return self->bases[b]->cids[gs - self->base_off[b]];
}

PyTypeObject *g_intents_type = nullptr;
PyTypeObject *g_intents_iter_type = nullptr;

// Intents objects are deliberately NOT GC-tracked: the only reference
// cycle they can sit on runs through the decode-table capsule, which
// is itself invisible to the cycle collector (capsules are never
// tracked) and is broken manually by table_release — so tracking buys
// no collectable cycle while making every GC pass walk the hundreds
// of thousands of cached results, and every cache clear a multi-second
// GC storm (measured: a recurring ~40x whole-batch stall at each
// icache fill). Nothing else can close a cycle onto an intents object:
// its referents are str client ids, plain Subscription records, dicts
// of those, the capsule, and an (acyclic) base intents. tp_traverse /
// tp_clear remain implemented for the HAVE_GC protocol and dealloc.
// COROLLARY OF THE EXISTING IMMUTABILITY CONTRACT (decode_pairs
// docstring): consumers must never graft a reference back onto a
// result's Subscription records (e.g. sub.attr = intents) — results
// and their records are shared, immutable, and deep_copy()'d before
// any mutation, so such a cycle cannot legally arise; an illegal one
// would now be uncollectable.
IntentsObject *intents_alloc(PyObject *capsule, Py_ssize_t capacity) {
  auto *self = PyObject_GC_New(IntentsObject, g_intents_type);
  if (!self) return nullptr;
  self->table_cap = Py_NewRef(capsule);
  self->n = 0;
  self->cids = nullptr;
  self->subs = nullptr;
  self->owned = nullptr;
  self->shared = nullptr;
  self->set_cache = nullptr;
  self->bases = nullptr;
  self->base_off = nullptr;
  self->n_bases = 0;
  self->ovr_slots = nullptr;
  self->ovr_subs = nullptr;
  self->n_ovr = 0;
  self->sel_seen = 0;
  if (capacity) {
    // one block for all three arrays (cids | subs | owned): chain
    // tails allocate per cold topic, so two fewer malloc/free pairs
    // per result is measurable; intents_clear_slot frees cids only
    char *block = static_cast<char *>(
        PyMem_Malloc(capacity * (2 * sizeof(PyObject *) + 1)));
    if (!block) {
      Py_DECREF(self);
      PyErr_NoMemory();
      return nullptr;
    }
    self->cids = reinterpret_cast<PyObject **>(block);
    self->subs = reinterpret_cast<PyObject **>(
        block + capacity * sizeof(PyObject *));
    self->owned = reinterpret_cast<uint8_t *>(
        block + 2 * capacity * sizeof(PyObject *));
  }
  return self;
}

int intents_traverse(PyObject *self_o, visitproc visit, void *arg) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  Py_VISIT(self->table_cap);
  Py_VISIT(self->shared);
  Py_VISIT(self->set_cache);
  for (int32_t b = 0; b < self->n_bases; b++)
    Py_VISIT(reinterpret_cast<PyObject *>(self->bases[b]));
  for (Py_ssize_t i = 0; i < self->n; i++)
    if (self->owned && self->owned[i]) Py_VISIT(self->subs[i]);
  for (Py_ssize_t i = 0; i < self->n_ovr; i++)
    Py_VISIT(self->ovr_subs[i]);
  return 0;
}

int intents_clear_slot(PyObject *self_o) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  if (self->owned)
    for (Py_ssize_t i = 0; i < self->n; i++)
      if (self->owned[i]) Py_CLEAR(self->subs[i]);
  self->n = 0;
  PyMem_Free(self->cids);  // one block carries cids+subs+owned
  self->cids = self->subs = nullptr;
  self->owned = nullptr;
  for (Py_ssize_t i = 0; i < self->n_ovr; i++)
    Py_CLEAR(self->ovr_subs[i]);
  self->n_ovr = 0;
  PyMem_Free(self->ovr_subs);  // one block: ovr_subs | ovr_slots
  self->ovr_slots = nullptr;
  self->ovr_subs = nullptr;
  for (int32_t b = 0; b < self->n_bases; b++)
    Py_CLEAR(self->bases[b]);
  self->n_bases = 0;
  PyMem_Free(self->bases);     // one block: bases | base_off
  self->bases = nullptr;
  self->base_off = nullptr;
  Py_CLEAR(self->table_cap);
  Py_CLEAR(self->shared);
  Py_CLEAR(self->set_cache);
  return 0;
}

void intents_dealloc(PyObject *self_o) {
  PyObject_GC_UnTrack(self_o);
  intents_clear_slot(self_o);
  PyTypeObject *tp = Py_TYPE(self_o);
  PyObject_GC_Del(self_o);
  Py_DECREF(tp);
}

Py_ssize_t intents_len(PyObject *self_o) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  Py_ssize_t n = intents_total(self);
  if (self->shared) {
    PyObject *k, *v;
    Py_ssize_t pos = 0;
    while (PyDict_Next(self->shared, &pos, &k, &v)) n += PyDict_Size(v);
  }
  return n;
}

// fresh plain-delivery dict: base entries first, shadowed by slot
// overrides, then the own tail
PyObject *intents_build_subs(const IntentsObject *self) {
  PyObject *subs = PyDict_New();
  if (!subs) return nullptr;
  for (int32_t b = 0; b < self->n_bases; b++) {
    const IntentsObject *bb = self->bases[b];
    for (Py_ssize_t j = 0; j < bb->n; j++)
      if (PyDict_SetItem(subs, bb->cids[j], bb->subs[j]) < 0) {
        Py_DECREF(subs);
        return nullptr;
      }
  }
  for (Py_ssize_t k = 0; k < self->n_ovr; k++)
    if (PyDict_SetItem(subs, base_cid_at(self, self->ovr_slots[k]),
                       self->ovr_subs[k]) < 0) {
      Py_DECREF(subs);
      return nullptr;
    }
  for (Py_ssize_t i = 0; i < self->n; i++)
    if (PyDict_SetItem(subs, self->cids[i], self->subs[i]) < 0) {
      Py_DECREF(subs);
      return nullptr;
    }
  return subs;
}

// to_set() -> SubscriberSet (cached): the hook-path materialization
PyObject *intents_to_set(PyObject *self_o, PyObject *) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  if (self->set_cache) return Py_NewRef(self->set_cache);
  PyObject *subs = intents_build_subs(self);
  if (!subs) return nullptr;
  // outer dict is fresh (callers re-wrap/copy it before dropping keys);
  // inner member dicts may be shared — consumers never mutate them
  PyObject *shared =
      self->shared ? PyDict_Copy(self->shared) : PyDict_New();
  if (!shared) {
    Py_DECREF(subs);
    return nullptr;
  }
  auto *res = subset_new_fast(subs, shared);
  Py_DECREF(subs);
  Py_DECREF(shared);
  if (!res) return nullptr;
  self->set_cache = reinterpret_cast<PyObject *>(res);
  return Py_NewRef(self->set_cache);
}

// select_set() -> a fresh hook-ready SubscriberSet straight from the
// intents arrays: new outer dicts AND new inner shared dicts (the
// modify chain may add/drop/replace entries anywhere) over aliased
// records. Caching policy: the FIRST call builds directly without
// populating set_cache (a cold unique-topic stream would pay a double
// build for a cache it never rehits); a SECOND call proves the row set
// repeats, so it materializes the to_set() twin once and every later
// call is a PyDict_Copy — one materialization per re-hit row set.
PyObject *intents_select_set(PyObject *self_o, PyObject *) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  if (self->set_cache) return subset_select_copy(self->set_cache, nullptr);
  if (self->sel_seen) {
    PyObject *twin = intents_to_set(self_o, nullptr);
    if (!twin) return nullptr;
    PyObject *res = subset_select_copy(twin, nullptr);
    Py_DECREF(twin);
    return res;
  }
  self->sel_seen = 1;
  PyObject *subs = intents_build_subs(self);
  if (!subs) return nullptr;
  PyObject *shared = PyDict_New();
  if (!shared) {
    Py_DECREF(subs);
    return nullptr;
  }
  if (self->shared) {
    PyObject *k, *v;
    Py_ssize_t pos = 0;
    while (PyDict_Next(self->shared, &pos, &k, &v)) {
      PyObject *m = PyDict_Copy(v);
      if (!m || PyDict_SetItem(shared, k, m) < 0) {
        Py_XDECREF(m);
        Py_DECREF(subs);
        Py_DECREF(shared);
        return nullptr;
      }
      Py_DECREF(m);
    }
  }
  auto *res = subset_new_fast(subs, shared);
  Py_DECREF(subs);
  Py_DECREF(shared);
  return reinterpret_cast<PyObject *>(res);
}

// One flat run of a resolve walk; ``sub_at(i)`` is entry i's record.
// The probe reads each id's type and cached hash out of its str, and
// the ids of a fat row lie all over a heap of a million objects: a DRAM
// miss an entry, which is all the walk costs. Asking for the str
// kResolveAhead entries early overlaps the misses (the flooded
// 1M-filter cell: a publish that delivers nothing 38 -> 20 us of
// fan-out, PERF.md section 6, PR 26).
constexpr Py_ssize_t kResolveAhead = 12;

template <class SubAt>
static inline int resolve_run(Resolve *r, PyObject *const *cids,
                              Py_ssize_t n, SubAt sub_at) {
  for (Py_ssize_t i = 0; i < n && i < kResolveAhead; i++)
    PREFETCH_R(cids[i]);
  int rc = 0;
  for (Py_ssize_t i = 0; rc == 0 && i < n; i++) {
    if (i + kResolveAhead < n) PREFETCH_R(cids[i + kResolveAhead]);
    rc = resolve_entry(r, cids[i], sub_at(i));
  }
  return rc;
}

// resolve(registry): the walk of intents_iternext (own tail, then the
// bases with their slot overrides) testing membership only — no tuple
// and no frame for an entry without a session
PyObject *intents_resolve(PyObject *self_o, PyObject *const *args,
                          Py_ssize_t nargs) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  Resolve r;
  if (!resolve_begin(&r, args, nargs)) return nullptr;
  int rc = resolve_run(&r, self->cids, self->n,
                       [&](Py_ssize_t i) { return self->subs[i]; });
  Py_ssize_t oi = 0;  // cursor into ovr_slots: global slots ascend
  for (int32_t b = 0; rc == 0 && b < self->n_bases; b++) {
    const IntentsObject *bb = self->bases[b];
    const int32_t off = self->base_off[b];
    rc = resolve_run(&r, bb->cids, bb->n, [&](Py_ssize_t j) {
      while (oi < self->n_ovr && self->ovr_slots[oi] < off + j) oi++;
      return (oi < self->n_ovr && self->ovr_slots[oi] == off + j)
                 ? self->ovr_subs[oi]
                 : bb->subs[j];
    });
  }
  return resolve_finish(&r, rc, intents_total(self), self->shared);
}

PyObject *intents_get_shared(PyObject *self_o, void *) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  if (!self->shared) {
    self->shared = PyDict_New();
    if (!self->shared) return nullptr;
  }
  return Py_NewRef(self->shared);
}

PyObject *intents_get_n(PyObject *self_o, void *) {
  return PyLong_FromSsize_t(
      intents_total(reinterpret_cast<IntentsObject *>(self_o)));
}

PyObject *intents_get_chained(PyObject *self_o, void *) {
  return PyBool_FromLong(
      reinterpret_cast<IntentsObject *>(self_o)->n_bases > 0);
}

struct IntentsIterObject {
  PyObject_HEAD
  IntentsObject *it;  // strong
  Py_ssize_t i;
  Py_ssize_t oi;  // cursor into ovr_slots (ascending, so O(1) amort.)
  int32_t b;      // current base (global slots ascend with iteration)
};

PyObject *intents_iter(PyObject *self_o) {
  auto *iter = PyObject_GC_New(IntentsIterObject, g_intents_iter_type);
  if (!iter) return nullptr;
  iter->it = reinterpret_cast<IntentsObject *>(Py_NewRef(self_o));
  iter->i = 0;
  iter->oi = 0;
  iter->b = 0;
  PyObject_GC_Track(iter);
  return reinterpret_cast<PyObject *>(iter);
}

PyObject *intents_iternext(PyObject *self_o) {
  auto *self = reinterpret_cast<IntentsIterObject *>(self_o);
  IntentsObject *v = self->it;
  const Py_ssize_t i = self->i;
  if (i < v->n) {
    self->i++;
    return PyTuple_Pack(2, v->cids[i], v->subs[i]);
  }
  if (!v->n_bases) return nullptr;  // StopIteration
  const Py_ssize_t j = i - v->n;    // global base slot
  if (j >= v->base_off[v->n_bases]) return nullptr;
  while (j >= v->base_off[self->b + 1]) self->b++;
  const IntentsObject *bb = v->bases[self->b];
  const Py_ssize_t lj = j - v->base_off[self->b];
  self->i++;
  while (self->oi < v->n_ovr && v->ovr_slots[self->oi] < j) self->oi++;
  PyObject *sub = (self->oi < v->n_ovr && v->ovr_slots[self->oi] == j)
                      ? v->ovr_subs[self->oi]
                      : bb->subs[lj];
  return PyTuple_Pack(2, bb->cids[lj], sub);
}

int intents_iter_traverse(PyObject *self_o, visitproc visit, void *arg) {
  Py_VISIT(reinterpret_cast<IntentsIterObject *>(self_o)->it);
  return 0;
}

void intents_iter_dealloc(PyObject *self_o) {
  PyObject_GC_UnTrack(self_o);
  Py_CLEAR(reinterpret_cast<IntentsIterObject *>(self_o)->it);
  PyTypeObject *tp = Py_TYPE(self_o);
  PyObject_GC_Del(self_o);
  Py_DECREF(tp);
}

PyObject *intents_repr(PyObject *self_o) {
  auto *self = reinterpret_cast<IntentsObject *>(self_o);
  if (self->n_bases)
    return PyUnicode_FromFormat(
        "DeliveryIntents(n=%zd, tail=%zd, bases=%d, overrides=%zd, "
        "shared=%zd)",
        intents_total(self), self->n, (int)self->n_bases, self->n_ovr,
        self->shared ? PyDict_Size(self->shared) : (Py_ssize_t)0);
  return PyUnicode_FromFormat(
      "DeliveryIntents(n=%zd, shared=%zd)", self->n,
      self->shared ? PyDict_Size(self->shared) : (Py_ssize_t)0);
}

PyMethodDef intents_methods[] = {
    {"to_set", intents_to_set, METH_NOARGS,
     "Materialize (and cache) the SubscriberSet twin for hook paths."},
    {"select_set", intents_select_set, METH_NOARGS,
     "Fresh hook-ready SubscriberSet (new dicts, aliased records)."},
    {"resolve", reinterpret_cast<PyCFunction>(intents_resolve), METH_FASTCALL,
     "(pairs, shared, matched, resolved) against the registry dict; its "
     "kept $share counts, if given, spare the walk of a known map."},
    {nullptr, nullptr, 0, nullptr}};

PyGetSetDef intents_getset[] = {
    {"shared", intents_get_shared, nullptr,
     "(group, filter) -> {client_id: Subscription} candidates", nullptr},
    {"n", intents_get_n, nullptr, "plain delivery entry count", nullptr},
    {"chained", intents_get_chained, nullptr,
     "True when anchored on a cached fat-row base fragment", nullptr},
    {nullptr, nullptr, nullptr, nullptr, nullptr}};

PyType_Slot intents_slots[] = {
    {Py_tp_doc, const_cast<char *>(
         "Per-topic delivery intents: iterable of (client_id, "
         "Subscription) plus shared-group candidate maps — the "
         "fan-out-ready decode result that skips merged-dict "
         "construction. Immutable; shared across topics and calls.")},
    {Py_tp_dealloc, reinterpret_cast<void *>(intents_dealloc)},
    {Py_tp_traverse, reinterpret_cast<void *>(intents_traverse)},
    {Py_tp_clear, reinterpret_cast<void *>(intents_clear_slot)},
    {Py_tp_methods, intents_methods},
    {Py_tp_getset, intents_getset},
    {Py_tp_iter, reinterpret_cast<void *>(intents_iter)},
    {Py_sq_length, reinterpret_cast<void *>(intents_len)},
    {Py_tp_repr, reinterpret_cast<void *>(intents_repr)},
    {0, nullptr}};

PyType_Spec intents_spec = {
    "maxmq_decode.DeliveryIntents", sizeof(IntentsObject), 0,
    Py_TPFLAGS_DEFAULT | Py_TPFLAGS_HAVE_GC | Py_TPFLAGS_DISALLOW_INSTANTIATION,
    intents_slots};

PyType_Slot intents_iter_slots[] = {
    {Py_tp_dealloc, reinterpret_cast<void *>(intents_iter_dealloc)},
    {Py_tp_traverse, reinterpret_cast<void *>(intents_iter_traverse)},
    {Py_tp_iter, reinterpret_cast<void *>(PyObject_SelfIter)},
    {Py_tp_iternext, reinterpret_cast<void *>(intents_iternext)},
    {0, nullptr}};

PyType_Spec intents_iter_spec = {
    "maxmq_decode._DeliveryIntentsIter", sizeof(IntentsIterObject), 0,
    Py_TPFLAGS_DEFAULT | Py_TPFLAGS_HAVE_GC | Py_TPFLAGS_DISALLOW_INSTANTIATION,
    intents_iter_slots};

// configure(merge_fn, copy_sub_fn) — register the python semantics
PyObject *configure(PyObject *, PyObject *args) {
  PyObject *merge, *copy;
  if (!PyArg_ParseTuple(args, "OO", &merge, &copy)) return nullptr;
  Py_XSETREF(g_merge_fn, Py_NewRef(merge));
  Py_XSETREF(g_copy_sub, Py_NewRef(copy));
  Py_RETURN_NONE;
}

// the chained union must be indistinguishable from the full union —
// this test-only switch lets the suite A/B the two builds of the SAME
// row set (flags included, not just the normalize() projection)
bool g_chain_enabled = true;
bool g_multi_base = true;

// chain-decision thresholds (settable for measurement/tests): anchor
// on the fattest row when it has >= min_base plain entries and the
// tail is at most (tail_num/tail_den) of it. Cost model: a tail pair
// costs one slot-map probe (~30ns) on top of the scratch work it pays
// either way, while every base pair SKIPS its ~43ns mark-table visit —
// so chaining pays off whenever fat*43 > tail*30, with min_base
// amortizing the fixed per-chain overhead (base lookup + override
// machinery). Defaults measured on the 1M bench corpus (see ADR 007).
Py_ssize_t g_chain_min_base = 64;
Py_ssize_t g_chain_tail_num = 1;
Py_ssize_t g_chain_tail_den = 1;

PyObject *set_chain_enabled(PyObject *, PyObject *arg) {
  const int v = PyObject_IsTrue(arg);
  if (v < 0) return nullptr;
  g_chain_enabled = v != 0;
  Py_RETURN_NONE;
}

PyObject *set_multi_base(PyObject *, PyObject *arg) {
  const int v = PyObject_IsTrue(arg);
  if (v < 0) return nullptr;
  g_multi_base = v != 0;
  Py_RETURN_NONE;
}

PyObject *set_chain_params(PyObject *, PyObject *args) {
  Py_ssize_t mb, num, den;
  if (!PyArg_ParseTuple(args, "nnn", &mb, &num, &den)) return nullptr;
  // the ratio test multiplies pair counts by num/den — bound them so
  // the products cannot overflow Py_ssize_t (pair counts < 2^40)
  if (mb < 1 || num < 0 || num > (1 << 20) || den < 1 ||
      den > (1 << 20)) {
    PyErr_SetString(PyExc_ValueError, "invalid chain params");
    return nullptr;
  }
  g_chain_min_base = mb;
  g_chain_tail_num = num;
  g_chain_tail_den = den;
  Py_RETURN_NONE;
}

PyObject *get_chain_params(PyObject *, PyObject *) {
  return Py_BuildValue("(nnn)", g_chain_min_base, g_chain_tail_num,
                       g_chain_tail_den);
}

// ----------------------------------------------------------------- //
//  Decode table + batch                                             //
// ----------------------------------------------------------------- //

struct DecodeTable {
  Py_buffer tok;        // int32 [R, W] row-major
  Py_buffer min_depth;  // int32 [R]
  Py_buffer flags;      // uint8 [R]
  Py_buffer offsets;    // int64 [R + 1] action CSR
  Py_buffer kinds;      // uint8 [A]
  PyObject *keys;       // list len A: filter str (PLAIN/MERGE) or
                        //             (group, filter) tuple (SHARED)
  PyObject *cids;       // list len A: client-id str
  PyObject *subs;       // list len A: Subscription
  PyObject *cache;      // verified-row-set bytes -> SubscriberSet
  PyObject *frag;       // row int -> single-row SubscriberSet fragment
  PyObject *icache;     // verified-row-set bytes -> DeliveryIntents
  Py_ssize_t cache_pairs = 0;  // subscriber entries in the row-set cache
  Py_ssize_t frag_pairs = 0;   // subscriber entries in the fragment cache
  Py_ssize_t icache_pairs = 0;  // entries in the intents cache
  // hits since the last clear, per result cache: a full cache that is
  // EARNING hits clears and rebuilds (hot set shifted); a full cache on
  // a unique-topic stream has nothing to rebuild FOR, so new entries
  // are simply not admitted — wholesale clear+refill churn was slower
  // than not caching at all (cold 1M stream measured 24K topics/s
  // thrashing vs 42K without the churn)
  Py_ssize_t cache_hits = 0;
  Py_ssize_t icache_hits = 0;
  Py_ssize_t cache_skips = 0;   // admissions refused since last clear
  Py_ssize_t icache_skips = 0;
  std::vector<PyObject *> key, cid, sub;  // borrowed from the lists
  // intents union scratch: per-action interned client index + an
  // epoch-stamped per-client slot map (no per-topic clearing). Epoch
  // and slot PACK into one uint64 per client — at 1M clients the
  // scratch lives in DRAM and the random per-action lookup is the
  // cold-union wall, so one cache miss per action beats two. The
  // scratch is SINGLE-BUILDER: merge_subscription callbacks (and any
  // allocation-triggered GC) can release the GIL mid-build, letting a
  // second executor thread enter cached_intents_result on the same
  // table — scratch_busy hands that builder a local-map fallback so
  // the stamps cannot be corrupted into duplicate deliveries.
  std::vector<int32_t> act_cidx;  // [A]; -1 for shared actions
  std::vector<uint64_t> mark;     // [n_clients] (epoch32 << 32) | slot
  int64_t epoch = 0;
  bool scratch_busy = false;
  // per-row prebuilt shared-group maps, built lazily ONCE per table:
  // a row's shared candidates are static table data, so the per-topic
  // shared assembly is a Py_NewRef (one shared row) or a bulk
  // PyDict_Copy + per-row inserts (several) instead of 2 dict ops per
  // (group, member) pair per topic — the measured wall of the cold
  // intents union on $share-heavy corpora (plain entries are pointer
  // writes; shared entries were ~300ns of hashing each). The maps are
  // immutable once published (the same aliased-inner-dict contract
  // to_set() already imposes on consumers).
  std::vector<PyObject *> rshared;  // [R]; nullptr until first touch
  std::vector<int32_t> shcount;     // [R] shared pairs in row's stream
  PyObject *empty_intents = nullptr;  // shared zero-entry result
  // chained-intents base support: per fat row, client index ->
  // (slot in the row's single-row intents, index of the row's action
  // for that client). The slot addresses iteration overrides; the
  // action index lets an override replay the base contribution through
  // merge_subscription exactly where the ascending-row-order union
  // would have applied it. Built lazily the first time a row anchors a
  // chain; rows that qualify are the few hundred-entry shallow-'#'
  // buckets, so the maps are small and live as long as the table (and
  // are dropped by table_release on rotation). slot_entries caps total
  // memory against pathological corpora (past it, new rows fall back
  // to the full union — correctness is unaffected).
  struct BaseSlot {
    int32_t slot;
    int64_t act;
  };
  std::unordered_map<int32_t, std::unordered_map<int32_t, BaseSlot>>
      row_slot;
  Py_ssize_t slot_entries = 0;
  // strong ref per fat row to its single-row intents: chains fetch the
  // base by one map probe instead of a key-bytes + icache round trip
  // per topic, and the base survives icache churn. Same
  // capsule<->cache cycle class as icache; table_release breaks it.
  std::unordered_map<int32_t, PyObject *> row_base;
  // multi-base composition: per-row purity flag (0 = none of the row's
  // plain clients appears in any other row), computed once at
  // table_new. Pure rows are pairwise disjoint with everything.
  std::vector<uint8_t> row_impure;
  Py_ssize_t R, W, A;
};

// A full cache whose entries earn no hits refuses new admissions (a
// unique-topic stream would otherwise clear+refill wholesale — measured
// SLOWER than not caching), but refusal is not forever: after
// kAdmissionRetry refused misses the cache clears and rebuilds anyway,
// so a hot set that shifted to uncached topics gets in within one
// bounded window instead of being locked out.
constexpr Py_ssize_t kAdmissionRetry = 65536;
// ... and a full cache clears ONLY when its entries were genuinely
// earning (a shifted hot set racks hits up fast). Requiring a single
// hit was enough for round-3's fat entries, but true-cost charging
// admits ~250K chains per budget — a mostly-cold stream with a few
// incidental repeats then cleared + rebuilt hundreds of thousands of
// GC-tracked objects at every fill (measured as a recurring ~40x
// whole-batch stall: the alloc/dealloc storm drives repeated full GC
// passes over a millions-of-objects heap).
constexpr Py_ssize_t kClearMinHits = 4096;

// Each cache (fragments, row-set unions) is bounded by the TOTAL
// subscriber entries it physically holds (hot corpora cache few, fat
// sets — a per-key cap would let 100K x 400-entry sets grow to GBs);
// past the cap that dict is dropped. The budgets are SEPARATE: a
// multi-row union is a real dict copy of its base fragment plus the
// delta (PyDict_Copy allocates fresh slots; only the Subscription
// values are shared), so it is charged its full pair count against the
// row-set budget — while fragment storage, charged once to its own
// budget, no longer halves the row-set cache's effective capacity
// (ADVICE r03 low). The table rotates on every subscription change.
constexpr Py_ssize_t kDecodeCachePairsCap = 4 << 20;

void table_destroy(PyObject *capsule) {
  auto *t = static_cast<DecodeTable *>(
      PyCapsule_GetPointer(capsule, "maxmq_decode.table"));
  if (!t) return;
  for (auto &kv : t->row_base) Py_XDECREF(kv.second);
  for (PyObject *d : t->rshared) Py_XDECREF(d);
  PyBuffer_Release(&t->tok);
  PyBuffer_Release(&t->min_depth);
  PyBuffer_Release(&t->flags);
  PyBuffer_Release(&t->offsets);
  PyBuffer_Release(&t->kinds);
  Py_XDECREF(t->keys);
  Py_XDECREF(t->cids);
  Py_XDECREF(t->subs);
  Py_XDECREF(t->cache);
  Py_XDECREF(t->frag);
  Py_XDECREF(t->icache);
  Py_XDECREF(t->empty_intents);
  delete t;
}

// table_new(tok, min_depth, flags, offsets, kinds, keys, cids, subs)
//   -> capsule
PyObject *table_new(PyObject *, PyObject *args) {
  PyObject *tok_o, *md_o, *fl_o, *off_o, *kind_o;
  PyObject *keys, *cids, *subs;
  if (!PyArg_ParseTuple(args, "OOOOOOOO", &tok_o, &md_o, &fl_o, &off_o,
                        &kind_o, &keys, &cids, &subs))
    return nullptr;
  if (!g_merge_fn) {
    PyErr_SetString(PyExc_RuntimeError, "configure() not called");
    return nullptr;
  }
  auto t = new DecodeTable();
  t->tok.obj = t->min_depth.obj = t->flags.obj = nullptr;
  t->offsets.obj = t->kinds.obj = nullptr;
  t->keys = t->cids = t->subs = t->cache = t->frag = nullptr;
  PyObject *capsule = PyCapsule_New(t, "maxmq_decode.table",
                                    table_destroy);
  if (!capsule) {
    delete t;
    return nullptr;
  }
  auto fail = [&](const char *msg) -> PyObject * {
    if (msg) PyErr_SetString(PyExc_ValueError, msg);
    Py_DECREF(capsule);  // destructor releases whatever was acquired
    return nullptr;
  };
  if (PyObject_GetBuffer(tok_o, &t->tok, PyBUF_SIMPLE) < 0 ||
      PyObject_GetBuffer(md_o, &t->min_depth, PyBUF_SIMPLE) < 0 ||
      PyObject_GetBuffer(fl_o, &t->flags, PyBUF_SIMPLE) < 0 ||
      PyObject_GetBuffer(off_o, &t->offsets, PyBUF_SIMPLE) < 0 ||
      PyObject_GetBuffer(kind_o, &t->kinds, PyBUF_SIMPLE) < 0)
    return fail(nullptr);
  if (!PyList_Check(keys) || !PyList_Check(cids) || !PyList_Check(subs))
    return fail("keys/cids/subs must be lists");
  t->R = (Py_ssize_t)t->flags.len;
  t->A = PyList_GET_SIZE(keys);
  if ((Py_ssize_t)t->min_depth.len != t->R * 4 ||
      (Py_ssize_t)t->offsets.len != (t->R + 1) * 8 ||
      (Py_ssize_t)t->kinds.len != t->A ||
      PyList_GET_SIZE(cids) != t->A || PyList_GET_SIZE(subs) != t->A ||
      (t->R && t->tok.len % (t->R * 4) != 0))
    return fail("table array lengths disagree");
  const auto *off = static_cast<const int64_t *>(t->offsets.buf);
  if (off[0] != 0 || off[t->R] != t->A)
    return fail("offsets do not span the action stream");
  for (Py_ssize_t r = 0; r < t->R; r++)
    if (off[r] > off[r + 1]) return fail("offsets not monotonic");
  t->W = t->R ? t->tok.len / (t->R * 4) : 0;
  t->keys = Py_NewRef(keys);
  t->cids = Py_NewRef(cids);
  t->subs = Py_NewRef(subs);
  t->cache = PyDict_New();
  t->frag = PyDict_New();
  t->icache = PyDict_New();
  if (!t->cache || !t->frag || !t->icache) return fail(nullptr);
  t->key.resize(t->A);
  t->cid.resize(t->A);
  t->sub.resize(t->A);
  for (Py_ssize_t a = 0; a < t->A; a++) {
    t->key[a] = PyList_GET_ITEM(keys, a);  // borrowed; lists hold refs
    t->cid[a] = PyList_GET_ITEM(cids, a);
    t->sub[a] = PyList_GET_ITEM(subs, a);
  }
  // intern client ids to dense indices for the intents union scratch
  {
    const auto *kind = static_cast<const uint8_t *>(t->kinds.buf);
    t->act_cidx.resize(t->A);
    PyObject *interned = PyDict_New();
    if (!interned) return fail(nullptr);
    Py_ssize_t C = 0;
    for (Py_ssize_t a = 0; a < t->A; a++) {
      if (kind[a] == ACT_SHARED) {
        t->act_cidx[a] = -1;
        continue;
      }
      PyObject *idx = PyDict_GetItemWithError(interned, t->cid[a]);
      if (idx) {
        t->act_cidx[a] = static_cast<int32_t>(PyLong_AsSsize_t(idx));
      } else {
        if (PyErr_Occurred()) {
          Py_DECREF(interned);
          return fail(nullptr);
        }
        PyObject *nv = PyLong_FromSsize_t(C);
        if (!nv || PyDict_SetItem(interned, t->cid[a], nv) < 0) {
          Py_XDECREF(nv);
          Py_DECREF(interned);
          return fail(nullptr);
        }
        Py_DECREF(nv);
        t->act_cidx[a] = static_cast<int32_t>(C++);
      }
    }
    Py_DECREF(interned);
    t->mark.assign(C, 0);
  }
  {
    const auto *kind = static_cast<const uint8_t *>(t->kinds.buf);
    const auto *offs = static_cast<const int64_t *>(t->offsets.buf);
    t->rshared.assign(t->R, nullptr);
    t->shcount.assign(t->R, 0);
    for (Py_ssize_t r = 0; r < t->R; r++) {
      int32_t c = 0;
      for (int64_t a = offs[r]; a < offs[r + 1]; a++)
        c += kind[a] == ACT_SHARED;
      t->shcount[r] = c;
    }
    // row purity for multi-base chaining: a client delivering plainly
    // from >= 2 rows makes every such row IMPURE. Pure rows share no
    // client with any other row, so any set of pure rows (plus at most
    // one impure row) is pairwise disjoint by construction — an O(1)
    // verdict at chain time instead of per-pair stream probes (pairs,
    // like subsets, almost never repeat on cold streams).
    {
      std::vector<uint8_t> cnt(t->mark.size(), 0);
      for (Py_ssize_t a = 0; a < t->A; a++)
        if (kind[a] != ACT_SHARED && t->act_cidx[a] >= 0) {
          uint8_t &x = cnt[t->act_cidx[a]];
          if (x < 2) x++;
        }
      t->row_impure.assign(t->R, 0);
      for (Py_ssize_t r = 0; r < t->R; r++)
        for (int64_t a = offs[r]; a < offs[r + 1]; a++)
          if (kind[a] != ACT_SHARED && t->act_cidx[a] >= 0 &&
              cnt[t->act_cidx[a]] >= 2) {
            t->row_impure[r] = 1;
            break;
          }
    }
  }
  return capsule;
}

// table_release(capsule) — break the table->caches->intents->capsule
// reference cycle when the python side drops a compiled snapshot.
// Capsules are not GC-tracked, so without this the whole table (token
// arrays, entry lists, every cached result) would leak on rotation.
// Outstanding handed-out results still hold the capsule and stay valid;
// only the table-held caches are dropped.
PyObject *table_release(PyObject *, PyObject *cap) {
  auto *t = static_cast<DecodeTable *>(
      PyCapsule_GetPointer(cap, "maxmq_decode.table"));
  if (!t) return nullptr;
  if (t->cache) PyDict_Clear(t->cache);
  if (t->frag) PyDict_Clear(t->frag);
  if (t->icache) PyDict_Clear(t->icache);
  Py_CLEAR(t->empty_intents);
  for (PyObject *&d : t->rshared) Py_CLEAR(d);
  t->cache_pairs = t->frag_pairs = t->icache_pairs = 0;
  t->cache_hits = t->icache_hits = 0;
  t->cache_skips = t->icache_skips = 0;
  t->row_slot.clear();
  t->slot_entries = 0;
  for (auto &kv : t->row_base) Py_DECREF(kv.second);
  t->row_base.clear();
  Py_RETURN_NONE;
}

inline int32_t topic_tok(const void *base, int mode, int32_t pad,
                         Py_ssize_t t, Py_ssize_t W, Py_ssize_t i) {
  int32_t v;
  switch (mode) {
    case 1: v = static_cast<const uint8_t *>(base)[t * W + i]; break;
    case 2: v = static_cast<const uint16_t *>(base)[t * W + i]; break;
    default: v = static_cast<const int32_t *>(base)[t * W + i]; break;
  }
  return v == pad ? -1 : v;
}

// result[t] as a SubscriberSet, materialized on first touch
inline SubSetObject *lazy_set(PyObject *list, Py_ssize_t t) {
  PyObject *s = PyList_GET_ITEM(list, t);
  if (s != Py_None) return reinterpret_cast<SubSetObject *>(s);
  auto *n = subset_new_fast(nullptr, nullptr);
  if (!n) return nullptr;
  PyList_SetItem(list, t, reinterpret_cast<PyObject *>(n));  // steals
  return n;
}

// replay row r's action stream into res; -1 on python error
int apply_row_actions(DecodeTable *t, SubSetObject *res, int64_t r) {
  const auto *off = static_cast<const int64_t *>(t->offsets.buf);
  const auto *kind = static_cast<const uint8_t *>(t->kinds.buf);
  for (int64_t a = off[r]; a < off[r + 1]; a++) {
    switch (kind[a]) {
      case ACT_PLAIN: {
        PyObject *cur =
            PyDict_GetItemWithError(res->subscriptions, t->cid[a]);
        if (!cur) {
          if (PyErr_Occurred() ||
              PyDict_SetItem(res->subscriptions, t->cid[a],
                             t->sub[a]) < 0)
            return -1;
        } else if (cur != t->sub[a]) {  // same-client collision
          PyObject *mg = PyObject_CallFunctionObjArgs(
              g_merge_fn, cur, t->sub[a], t->key[a], nullptr);
          if (!mg ||
              PyDict_SetItem(res->subscriptions, t->cid[a], mg) < 0) {
            Py_XDECREF(mg);
            return -1;
          }
          Py_DECREF(mg);
        }
        break;
      }
      case ACT_MERGE: {  // v5 identifiers: copy semantics via python
        PyObject *cur =
            PyDict_GetItemWithError(res->subscriptions, t->cid[a]);
        if (!cur && PyErr_Occurred()) return -1;
        PyObject *mg = PyObject_CallFunctionObjArgs(
            g_merge_fn, cur ? cur : Py_None, t->sub[a], t->key[a],
            nullptr);
        if (!mg ||
            PyDict_SetItem(res->subscriptions, t->cid[a], mg) < 0) {
          Py_XDECREF(mg);
          return -1;
        }
        Py_DECREF(mg);
        break;
      }
      default: {  // ACT_SHARED
        PyObject *g = PyDict_GetItemWithError(res->shared, t->key[a]);
        if (!g) {
          if (PyErr_Occurred()) return -1;
          g = PyDict_New();
          if (!g || PyDict_SetItem(res->shared, t->key[a], g) < 0) {
            Py_XDECREF(g);
            return -1;
          }
          Py_DECREF(g);  // res->shared holds the ref now
        }
        if (PyDict_SetItem(g, t->cid[a], t->sub[a]) < 0) return -1;
        break;
      }
    }
  }
  return 0;
}

// pairs held by one set (for the cache budget)
Py_ssize_t subset_pairs(SubSetObject *res) {
  Py_ssize_t pairs = PyDict_GET_SIZE(res->subscriptions);
  PyObject *gk, *gv;
  for (Py_ssize_t pos = 0; PyDict_Next(res->shared, &pos, &gk, &gv);)
    pairs += PyDict_GET_SIZE(gv);
  return pairs;
}

// build-or-fetch the single-row fragment for row r; BORROWED reference
// (owned by t->frag). Fragments are reused across topics even when
// their row-set combinations differ, so a multi-row cache miss costs a
// dict copy + the smaller rows' inserts instead of a full rebuild.
SubSetObject *fragment_for_row(DecodeTable *t, int32_t r) {
  PyObject *rk = PyLong_FromLong(r);
  if (!rk) return nullptr;
  PyObject *hit = PyDict_GetItemWithError(t->frag, rk);
  if (hit) {
    Py_DECREF(rk);
    return reinterpret_cast<SubSetObject *>(hit);
  }
  if (PyErr_Occurred()) {
    Py_DECREF(rk);
    return nullptr;
  }
  auto *res = subset_new_fast(nullptr, nullptr);
  if (!res || apply_row_actions(t, res, r) < 0) {
    Py_DECREF(rk);
    Py_XDECREF(res);
    return nullptr;
  }
  const Py_ssize_t pairs = subset_pairs(res);
  if (t->frag_pairs + pairs > kDecodeCachePairsCap) {
    // clear BOTH dicts: single-row entries in t->cache alias fragment
    // objects with pairs=0 charged, so dropping only t->frag would
    // leave up to a full cap of fragment storage alive-but-uncounted
    // through those aliases (resident could reach 3x cap); clearing
    // both restores the documented 2x-cap bound
    PyDict_Clear(t->frag);
    PyDict_Clear(t->cache);
    t->frag_pairs = 0;
    t->cache_pairs = 0;
    t->cache_hits = 0;
    t->cache_skips = 0;
  }
  const int rc = PyDict_SetItem(t->frag, rk,
                                reinterpret_cast<PyObject *>(res));
  Py_DECREF(rk);
  Py_DECREF(res);  // t->frag holds the ref; borrowed below
  if (rc < 0) return nullptr;
  t->frag_pairs += pairs;
  return res;
}

// build-or-fetch the merged SubscriberSet for one verified, sorted,
// deduped row set; returns a NEW reference (cached object shared across
// topics — callers treat results as immutable, deep_copy before
// mutating, the same discipline the broker's match cache imposes)
PyObject *cached_rowset_result(DecodeTable *t, const int32_t *rows,
                               Py_ssize_t n_rows) {
  PyObject *key = PyBytes_FromStringAndSize(
      reinterpret_cast<const char *>(rows),
      n_rows * (Py_ssize_t)sizeof(int32_t));
  if (!key) return nullptr;
  PyObject *hit = PyDict_GetItemWithError(t->cache, key);
  if (hit) {
    t->cache_hits++;
    Py_DECREF(key);
    return Py_NewRef(hit);
  }
  if (PyErr_Occurred()) {
    Py_DECREF(key);
    return nullptr;
  }
  // base the union on the FATTEST row: its fragment is bulk-copied
  // (PyDict_Copy clones the hash table without re-hashing) while the
  // other rows replay per-entry. On fan-out-heavy corpora one shallow
  // '#'-bucket row carries hundreds of entries and the rest a handful,
  // so base choice is the difference between a memcpy-ish copy and
  // hundreds of dict inserts per topic. Merge-order effects are
  // confined to which overlapping filter donates the RAP/RH flags —
  // arbitrary in the reference too (its trie iteration order); qos is
  // max and identifier union is commutative.
  const auto *off_b = static_cast<const int64_t *>(t->offsets.buf);
  Py_ssize_t bi = 0;
  for (Py_ssize_t i = 1; i < n_rows; i++)
    if (off_b[rows[i] + 1] - off_b[rows[i]] >
        off_b[rows[bi] + 1] - off_b[rows[bi]])
      bi = i;
  SubSetObject *res;
  SubSetObject *base = fragment_for_row(t, rows[bi]);
  if (!base) {
    Py_DECREF(key);
    return nullptr;
  }
  if (n_rows == 1) {
    res = reinterpret_cast<SubSetObject *>(
        Py_NewRef(reinterpret_cast<PyObject *>(base)));
  } else {
    // union = copy of the base fragment + the remaining rows' action
    // streams. Inner shared-group dicts must be copied too —
    // apply_row_actions mutates them on group collisions and
    // fragments are shared.
    PyObject *subs = PyDict_Copy(base->subscriptions);
    PyObject *shared =
        PyDict_GET_SIZE(base->shared) ? PyDict_Copy(base->shared)
                                      : nullptr;
    if (!subs || (PyDict_GET_SIZE(base->shared) && !shared)) {
      Py_XDECREF(subs);
      Py_XDECREF(shared);
      Py_DECREF(key);
      return nullptr;
    }
    if (shared) {
      PyObject *gk, *gv;
      for (Py_ssize_t pos = 0; PyDict_Next(shared, &pos, &gk, &gv);) {
        PyObject *cp = PyDict_Copy(gv);
        if (!cp || PyDict_SetItem(shared, gk, cp) < 0) {
          Py_XDECREF(cp);
          Py_DECREF(subs);
          Py_DECREF(shared);
          Py_DECREF(key);
          return nullptr;
        }
        Py_DECREF(cp);
      }
    }
    res = subset_new_fast(subs, shared);
    Py_DECREF(subs);
    Py_XDECREF(shared);
    if (!res) {
      Py_DECREF(key);
      return nullptr;
    }
    for (Py_ssize_t i = 0; i < n_rows; i++) {
      if (i == bi) continue;  // the base fragment already carries it
      if (apply_row_actions(t, res, rows[i]) < 0) {
        Py_DECREF(key);
        Py_DECREF(res);
        return nullptr;
      }
    }
  }
  // a single-row result ALIASES its fragment (no new dict storage —
  // its pairs live in the fragment budget); a multi-row union owns a
  // real copied dict and is charged in full against the row-set budget
  const Py_ssize_t pairs = n_rows == 1 ? 0 : subset_pairs(res);
  if (t->cache_pairs + pairs > kDecodeCachePairsCap) {
    if (t->cache_hits < kClearMinHits &&
        ++t->cache_skips < kAdmissionRetry) {
      Py_DECREF(key);              // cold stream: stop churning
      return reinterpret_cast<PyObject *>(res);
    }
    PyDict_Clear(t->cache);
    t->cache_pairs = 0;
    t->cache_hits = 0;
    t->cache_skips = 0;
  }
  int rc = PyDict_SetItem(t->cache, key, reinterpret_cast<PyObject *>(res));
  Py_DECREF(key);
  if (rc < 0) {
    Py_DECREF(res);
    return nullptr;
  }
  t->cache_pairs += pairs;
  return reinterpret_cast<PyObject *>(res);
}

// build-or-fetch row r's prebuilt shared-group map {(group, filter) ->
// {cid: sub}}; BORROWED reference (the table owns it). Built fully
// into a local dict and only then published: dict allocation can
// trigger GC, GC can run arbitrary finalizers, and a finalizer can
// re-enter this builder on another thread's behalf — publish-once
// keeps the cached map single and complete.
PyObject *row_shared(DecodeTable *t, Py_ssize_t r) {
  if (t->rshared[r]) return t->rshared[r];
  const auto *off = static_cast<const int64_t *>(t->offsets.buf);
  const auto *kind = static_cast<const uint8_t *>(t->kinds.buf);
  PyObject *d = PyDict_New();
  if (!d) return nullptr;
  for (int64_t a = off[r]; a < off[r + 1]; a++) {
    if (kind[a] != ACT_SHARED) continue;
    PyObject *g = PyDict_GetItemWithError(d, t->key[a]);
    if (!g) {
      if (PyErr_Occurred()) {
        Py_DECREF(d);
        return nullptr;
      }
      g = PyDict_New();
      if (!g || PyDict_SetItem(d, t->key[a], g) < 0) {
        Py_XDECREF(g);
        Py_DECREF(d);
        return nullptr;
      }
      Py_DECREF(g);
    }
    if (PyDict_SetItem(g, t->cid[a], t->sub[a]) < 0) {
      Py_DECREF(d);
      return nullptr;
    }
  }
  if (!t->rshared[r]) {
    t->rshared[r] = d;          // publish; table owns the ref
  } else {
    Py_DECREF(d);               // lost a re-entrant race: use the winner
  }
  return t->rshared[r];
}

// total per-table slot-map entry budget; a mutable global so the test
// suite can shrink it to exercise the prewarm budget paths without
// building hundred-thousand-entry corpora
Py_ssize_t g_slot_map_cap = 512 * 1024;

PyObject *cached_intents_result(DecodeTable *t, PyObject *cap,
                                const int32_t *rows, Py_ssize_t n_rows,
                                bool allow_chain = true);

// build-or-fetch row r's slot map and pinned single-row base intents
// (shared by the chain resolution loop and prewarm_bases). Returns the
// slot map, or nullptr when the map budget declines the row; *base_out
// gets a NEW reference to the base intents, or nullptr on a python
// error (PyErr set).
std::unordered_map<int32_t, DecodeTable::BaseSlot> *
ensure_row_base(DecodeTable *t, PyObject *cap, int32_t r, Py_ssize_t p,
                PyObject **base_out) {
  const auto *off = static_cast<const int64_t *>(t->offsets.buf);
  const auto *kind = static_cast<const uint8_t *>(t->kinds.buf);
  *base_out = nullptr;
  std::unordered_map<int32_t, DecodeTable::BaseSlot> *m;
  auto found = t->row_slot.find(r);
  if (found != t->row_slot.end()) {
    m = &found->second;
  } else if (t->slot_entries + p <= g_slot_map_cap) {
    m = &t->row_slot[r];
    m->reserve(static_cast<size_t>(p) * 2);
    int32_t slot = 0;
    for (int64_t a = off[r]; a < off[r + 1]; a++) {
      if (kind[a] == ACT_SHARED) continue;
      m->emplace(t->act_cidx[a], DecodeTable::BaseSlot{slot++, a});
    }
    t->slot_entries += p;
  } else {
    return nullptr;              // budget: row unions in the tail
  }
  PyObject *b;
  auto fb = t->row_base.find(r);
  if (fb != t->row_base.end()) {
    b = Py_NewRef(fb->second);
  } else {
    int32_t one = r;
    b = cached_intents_result(t, cap, &one, 1, true);
    if (!b) return m;            // PyErr set; *base_out stays null
    // the recursive build can run Python (merge callbacks, GC
    // finalizers) and re-enter this builder; only the emplace WINNER
    // may deposit a reference, like row_shared's publish-once
    // discipline
    auto ins = t->row_base.emplace(r, nullptr);
    if (ins.second) ins.first->second = Py_NewRef(b);
  }
  *base_out = b;
  return m;
}

// build-or-fetch DeliveryIntents for one verified, sorted, deduped row
// set; NEW reference. The union is an epoch-stamped dedupe over the
// rows' action streams — int32/pointer writes only; merge_subscription
// runs solely on same-client collisions and v5-identifier entries.
PyObject *cached_intents_result(DecodeTable *t, PyObject *cap,
                                const int32_t *rows, Py_ssize_t n_rows,
                                bool allow_chain) {
  PyObject *key = PyBytes_FromStringAndSize(
      reinterpret_cast<const char *>(rows),
      n_rows * (Py_ssize_t)sizeof(int32_t));
  if (!key) return nullptr;
  PyObject *hit = PyDict_GetItemWithError(t->icache, key);
  if (hit) {
    t->icache_hits++;
    Py_DECREF(key);
    return Py_NewRef(hit);
  }
  if (PyErr_Occurred()) {
    Py_DECREF(key);
    return nullptr;
  }
  const auto *off = static_cast<const int64_t *>(t->offsets.buf);
  const auto *kind = static_cast<const uint8_t *>(t->kinds.buf);
  Py_ssize_t total = 0;
  Py_ssize_t sh_pairs = 0;
  for (Py_ssize_t i = 0; i < n_rows; i++) {
    total += off[rows[i] + 1] - off[rows[i]];
    sh_pairs += t->shcount[rows[i]];
  }
  // chain decision (round-5 multi-base form): the union anchors on a
  // LIST of cached per-row base intents and builds only the thin
  // remainder — O(tail) per topic instead of O(total), the whole
  // cold-stream game on shallow-'#' corpora. Heavy cold sets look like
  // [~280, 63, 61, 50, thin...]: their fat-row COMBINATIONS almost
  // never repeat (measured: 2,781 distinct subsets across 2,783
  // multi-fat topics at 1M subs — a flattened per-subset base can
  // never amortize and measured strictly slower), but each ROW repeats
  // across many topics, so every row at or above base_min_row becomes
  // its own base. Bases must be pairwise client-disjoint (exact
  // verdicts cached per row pair); an overlapping row drops to the
  // tail, which keeps the fold semantics single-act per client.
  constexpr int kMaxBases = 8;
  const Py_ssize_t base_min_row =
      g_multi_base ? std::max<Py_ssize_t>(16, g_chain_min_base / 4)
                   : g_chain_min_base;
  Py_ssize_t total_plain = 0, sum_base = 0;
  Py_ssize_t cand[kMaxBases], cand_p[kMaxBases];
  int n_cand = 0;
  if (n_rows > 1 && g_chain_enabled && allow_chain) {
    for (Py_ssize_t i = 0; i < n_rows; i++) {
      const Py_ssize_t p =
          (off[rows[i] + 1] - off[rows[i]]) - t->shcount[rows[i]];
      total_plain += p;
      if (p >= base_min_row) {
        if (n_cand < kMaxBases) {
          cand[n_cand] = i;
          cand_p[n_cand] = p;
          n_cand++;
          sum_base += p;
        } else {
          // keep the FATTEST kMaxBases candidates: replace the
          // smallest (the fat anchor must never fall to the tail)
          int sm = 0;
          for (int cj = 1; cj < kMaxBases; cj++)
            if (cand_p[cj] < cand_p[sm]) sm = cj;
          if (p > cand_p[sm]) {
            sum_base += p - cand_p[sm];
            cand[sm] = i;
            cand_p[sm] = p;
          }
        }
      }
    }
    if (!g_multi_base && n_cand > 1) {
      // legacy form: only the fattest candidate anchors
      int best = 0;
      for (int ci = 1; ci < n_cand; ci++)
        if (cand_p[ci] > cand_p[best]) best = ci;
      cand[0] = cand[best];
      cand_p[0] = cand_p[best];
      n_cand = 1;
      sum_base = cand_p[0];
    }
    if (sum_base < g_chain_min_base ||
        (total_plain - sum_base) * g_chain_tail_den >
            sum_base * g_chain_tail_num) {
      n_cand = 0;
    }
  }

  // resolve candidates (ascending row order) into accepted bases:
  // slot map + pairwise disjointness + pinned single-row intents
  IntentsObject *bases_acc[kMaxBases];
  std::unordered_map<int32_t, DecodeTable::BaseSlot> *maps_acc[kMaxBases];
  int32_t base_rows[kMaxBases];
  Py_ssize_t base_ci[kMaxBases];  // candidate's index into rows[]
  int k = 0;
  Py_ssize_t kept_mass = 0;
  bool have_impure = false;
  auto drop_bases = [&]() {
    for (int j = 0; j < k; j++)
      Py_DECREF(reinterpret_cast<PyObject *>(bases_acc[j]));
    k = 0;
    kept_mass = 0;
  };
  // ascending row order (slot/base/fold invariants); the fattest-8
  // replacement above can leave cand[] unordered
  for (int a2 = 1; a2 < n_cand; a2++)
    for (int b2 = a2; b2 > 0 && cand[b2] < cand[b2 - 1]; b2--) {
      std::swap(cand[b2], cand[b2 - 1]);
      std::swap(cand_p[b2], cand_p[b2 - 1]);
    }
  for (int ci = 0; ci < n_cand; ci++) {
    const int32_t r = rows[cand[ci]];
    const Py_ssize_t p = cand_p[ci];
    // purity rule (O(1)): pure rows share no client with ANY other
    // row; an impure row may only be the single impure base
    if (t->row_impure[r] && have_impure)
      continue;                 // could overlap a kept base: tail it
    PyObject *b = nullptr;
    auto *m = ensure_row_base(t, cap, r, p, &b);
    if (!m) continue;           // budget: this row unions in the tail
    if (!b) {
      drop_bases();
      Py_DECREF(key);
      return nullptr;
    }
    if (t->row_impure[r]) have_impure = true;
    bases_acc[k] = reinterpret_cast<IntentsObject *>(b);
    maps_acc[k] = m;
    base_rows[k] = r;
    base_ci[k] = cand[ci];
    kept_mass += p;
    k++;
  }
  // dropped candidates grew the tail: the chain must still win
  if (k && (kept_mass < g_chain_min_base ||
            (total_plain - kept_mass) * g_chain_tail_den >
                kept_mass * g_chain_tail_num)) {
    drop_bases();
  }

  const bool chained = k > 0;
  const Py_ssize_t tail_n = chained ? total_plain - kept_mass : 0;
  IntentsObject *it =
      intents_alloc(cap, chained ? tail_n : total - sh_pairs);
  if (!it) {
    drop_bases();
    Py_DECREF(key);
    return nullptr;
  }
  std::vector<char> is_base_i;
  if (chained) {
    char *blk = static_cast<char *>(PyMem_Malloc(
        k * sizeof(IntentsObject *) + (k + 1) * sizeof(int32_t)));
    if (!blk) {
      drop_bases();
      Py_DECREF(key);
      Py_DECREF(it);
      PyErr_NoMemory();
      return nullptr;
    }
    it->bases = reinterpret_cast<IntentsObject **>(blk);
    it->base_off = reinterpret_cast<int32_t *>(
        blk + k * sizeof(IntentsObject *));
    it->base_off[0] = 0;
    for (int j = 0; j < k; j++) {
      it->bases[j] = bases_acc[j];  // ref transferred
      it->base_off[j + 1] =
          it->base_off[j] + static_cast<int32_t>(bases_acc[j]->n);
    }
    it->n_bases = k;
    is_base_i.assign(n_rows, 0);
    for (int j = 0; j < k; j++) is_base_i[base_ci[j]] = 1;
    if (tail_n) {
      // one block: PyObject* array first (alignment), slots after
      char *ob = static_cast<char *>(PyMem_Malloc(
          tail_n * (sizeof(PyObject *) + sizeof(int32_t))));
      if (!ob) {
        Py_DECREF(key);
        Py_DECREF(it);
        PyErr_NoMemory();
        return nullptr;
      }
      it->ovr_subs = reinterpret_cast<PyObject **>(ob);
      it->ovr_slots = reinterpret_cast<int32_t *>(
          ob + tail_n * sizeof(PyObject *));
    }
  }
  // override build state: a chained union must produce EXACTLY what
  // the ascending-row-order union produces for a client present in
  // both a base row and tail rows — qos max and identifier union
  // are order-free, but merge_subscription takes flags from the NEWER
  // (= higher row id) filter, so the base contribution is folded in
  // at its ordered position via its raw action, not merged
  // first-come. Bases are pairwise disjoint, so each client has at
  // most ONE base act.
  struct OvrBuild {
    int32_t slot;      // GLOBAL base slot shadowed
    int64_t base_act;  // the base row's action for this client
    int32_t base_row;  // its row (fold ordering)
    PyObject *cur;     // accumulated entry; owned iff owned
    bool owned;
    bool folded;       // base contribution already applied
  };
  std::vector<OvrBuild> ovr_build;
  std::unordered_map<int32_t, size_t> ovr_index;  // slot -> build idx
  auto bail = [&]() -> PyObject * {
    for (auto &ob : ovr_build)
      if (ob.owned) Py_XDECREF(ob.cur);
    Py_DECREF(key);
    Py_DECREF(it);
    return nullptr;
  };
  // shared-group map: assembled from the prebuilt per-row maps — one
  // Py_NewRef when a single row carries shared members, else a bulk
  // copy of the fattest row's map + per-group inserts (inner maps
  // merged copy-on-write on the rare duplicate-filter-row collision)
  Py_ssize_t sh_owned_pairs = 0;  // shared pairs this result STORES
                                  // (an aliased per-row map costs 0)
  if (sh_pairs) {
    Py_ssize_t sh_n = 0, base_i = -1;
    for (Py_ssize_t i = 0; i < n_rows; i++)
      if (t->shcount[rows[i]]) {
        sh_n++;
        if (base_i < 0 ||
            t->shcount[rows[i]] > t->shcount[rows[base_i]])
          base_i = i;
      }
    PyObject *b = row_shared(t, rows[base_i]);
    if (!b) return bail();
    if (sh_n == 1) {
      it->shared = Py_NewRef(b);  // aliased: no storage of its own
    } else {
      sh_owned_pairs = sh_pairs;
      PyObject *d = PyDict_Copy(b);
      if (!d) return bail();
      it->shared = d;            // owned; set before merging so a
                                 // failed merge frees it via bail
      for (Py_ssize_t i = 0; i < n_rows; i++) {
        if (i == base_i || !t->shcount[rows[i]]) continue;
        PyObject *rs = row_shared(t, rows[i]);
        if (!rs) return bail();
        PyObject *gk, *gv;
        for (Py_ssize_t pos = 0; PyDict_Next(rs, &pos, &gk, &gv);) {
          PyObject *cur = PyDict_GetItemWithError(d, gk);
          if (cur) {
            PyObject *cp = PyDict_Copy(cur);
            if (!cp || PyDict_Update(cp, gv) < 0 ||
                PyDict_SetItem(d, gk, cp) < 0) {
              Py_XDECREF(cp);
              return bail();
            }
            Py_DECREF(cp);
          } else {
            if (PyErr_Occurred()) return bail();
            if (PyDict_SetItem(d, gk, gv) < 0) return bail();
          }
        }
      }
    }
  }
  // single-builder fast scratch, local-map fallback for a concurrent
  // builder that entered while a Python callback had the GIL released
  struct ScratchGuard {
    DecodeTable *t;
    bool owned;
    explicit ScratchGuard(DecodeTable *tt)
        : t(tt), owned(!tt->scratch_busy) {
      if (owned) t->scratch_busy = true;
    }
    ~ScratchGuard() {
      if (owned) t->scratch_busy = false;
    }
  } guard(t);
  std::unordered_map<int32_t, Py_ssize_t> local_slot;
  // a SINGLE row's non-shared actions are distinct clients by
  // construction (one entry per (client, filter)), so the whole
  // dedupe apparatus — marks, epochs, prefetch — is skipped and the
  // union degenerates to a straight sequential copy of the stream.
  // A chained build unions only the tail rows, so the same shortcut
  // applies when the tail is a single row.
  const Py_ssize_t n_union_rows = n_rows - k;
  const bool dedupe = n_union_rows > 1;
  const bool fast = dedupe && guard.owned;
  uint32_t e32 = 0;
  if (fast) {
    ++t->epoch;
    if ((t->epoch & 0xFFFFFFFFll) == 0) {
      // epoch32 wrapped: a mark stamped exactly 2^32 unions ago would
      // falsely read as current — clear and skip the zero epoch
      std::fill(t->mark.begin(), t->mark.end(), 0);
      ++t->epoch;
    }
    e32 = static_cast<uint32_t>(t->epoch & 0xFFFFFFFFll);
  }
  auto slot_of = [&](int32_t c) -> Py_ssize_t {
    if (!dedupe) return -1;
    if (fast) {
      const uint64_t m = t->mark[c];
      return static_cast<uint32_t>(m >> 32) == e32
                 ? (Py_ssize_t)(uint32_t)m
                 : -1;
    }
    auto f = local_slot.find(c);
    return f == local_slot.end() ? -1 : f->second;
  };
  auto record_slot = [&](int32_t c, Py_ssize_t j) {
    if (!dedupe) return;
    if (fast) {
      t->mark[c] = (static_cast<uint64_t>(e32) << 32) |
                   static_cast<uint32_t>(j);
    } else {
      local_slot[c] = j;
    }
  };
  // fold the base row's contribution into an override at its ordered
  // position (no-op pointer-equality skip mirrors the union's
  // duplicate-filter-row shortcut)
  auto fold_base = [&](OvrBuild &ob) -> bool {
    if (ob.folded) return true;
    ob.folded = true;
    if (!ob.cur) {
      // base is this client's first contribution: the entry form the
      // union would hold after the base row (ACT_MERGE base actions
      // are already pre-merged inside the base intents)
      ob.cur = base_sub_at(it, ob.slot);
      ob.owned = false;
      return true;
    }
    if (kind[ob.base_act] == ACT_PLAIN && ob.cur == t->sub[ob.base_act])
      return true;  // same record twice (duplicate filter rows)
    PyObject *mg = PyObject_CallFunctionObjArgs(
        g_merge_fn, ob.cur, t->sub[ob.base_act], t->key[ob.base_act],
        nullptr);
    if (!mg) return false;
    if (ob.owned) Py_DECREF(ob.cur);
    ob.cur = mg;
    ob.owned = true;
    return true;
  };
  // Tail-collision probe gating: a client can sit in both a tail row
  // and a base row only if BOTH rows are impure (that is the purity
  // definition), and at most one kept base is impure — so pure tail
  // rows probe nothing, and impure ones probe exactly one map.
  int impure_j = -1;
  for (int j = 0; j < k; j++)
    if (t->row_impure[base_rows[j]]) impure_j = j;
  Py_ssize_t n = 0;
  // The union is DRAM-latency-bound: every action's mark[] slot is a
  // random 8-byte access into a table that is tens of MB at 1M clients
  // (measured 128ns/pair cold = one full miss each). Prefetching the
  // slot kPrefetch actions ahead (spilling into the next row's stream
  // at a segment boundary) overlaps the misses; the hardware sustains
  // ~10 in flight, turning the wall from latency- to bandwidth-bound.
  constexpr int64_t kPrefetch = 24;
  auto prefetch_at = [&](Py_ssize_t i, int64_t a) {
    int64_t pa = a + kPrefetch;
    int64_t pe = off[rows[i] + 1];
    if (pa >= pe) {
      if (i + 1 >= n_rows) return;
      const int64_t r2 = rows[i + 1];
      pa = off[r2] + (pa - pe);
      pe = off[r2 + 1];
      if (pa >= pe) return;
    }
    const int32_t pc = t->act_cidx[pa];
    if (pc >= 0) PREFETCH_W(&t->mark[pc]);
  };
  for (Py_ssize_t i = 0; i < n_rows; i++) {
    if (chained && is_base_i[i]) continue;  // bases carry these rows
    const int64_t r = rows[i];
    for (int64_t a = off[r]; a < off[r + 1]; a++) {
      if (fast) prefetch_at(i, a);
      const uint8_t kk = kind[a];
      if (kk == ACT_SHARED) continue;  // prebuilt per-row maps above
      const int32_t c = t->act_cidx[a];
      if (chained && impure_j >= 0 && t->row_impure[r]) {
        // same client also in a base row: only possible when both the
        // tail row and a base row are impure, and at most one kept
        // base is — probe exactly that one map
        const DecodeTable::BaseSlot *hit = nullptr;
        const int hit_j = impure_j;
        {
          auto f = maps_acc[hit_j]->find(c);
          if (f != maps_acc[hit_j]->end()) hit = &f->second;
        }
        if (hit) {
          const int32_t gslot = it->base_off[hit_j] + hit->slot;
          size_t oi;
          auto fi = ovr_index.find(gslot);
          if (fi != ovr_index.end()) {
            oi = fi->second;
          } else {
            oi = ovr_build.size();
            ovr_index.emplace(gslot, oi);
            ovr_build.push_back({gslot, hit->act, base_rows[hit_j],
                                 nullptr, false, false});
          }
          OvrBuild &ob = ovr_build[oi];
          if (ob.base_row < r && !fold_base(ob)) return bail();
          if (!ob.cur) {
            // first contribution, base row not yet due (r < base row)
            if (kk == ACT_MERGE) {
              PyObject *mg = PyObject_CallFunctionObjArgs(
                  g_merge_fn, Py_None, t->sub[a], t->key[a], nullptr);
              if (!mg) return bail();
              ob.cur = mg;
              ob.owned = true;
            } else {
              ob.cur = t->sub[a];
              ob.owned = false;
            }
          } else if (kk == ACT_PLAIN && ob.cur == t->sub[a]) {
            // same record twice (duplicate filter rows)
          } else {
            PyObject *mg = PyObject_CallFunctionObjArgs(
                g_merge_fn, ob.cur, t->sub[a], t->key[a], nullptr);
            if (!mg) return bail();
            if (ob.owned) Py_DECREF(ob.cur);
            ob.cur = mg;
            ob.owned = true;
          }
          continue;
        }
      }
      const Py_ssize_t j = slot_of(c);
      if (j < 0) {
        record_slot(c, n);
        it->cids[n] = t->cid[a];
        if (kk == ACT_MERGE) {
          // v5 identifiers: ALWAYS through merge_subscription so the
          // identifier-union copy semantics hold from the first insert
          PyObject *mg = PyObject_CallFunctionObjArgs(
              g_merge_fn, Py_None, t->sub[a], t->key[a], nullptr);
          if (!mg) return bail();
          it->subs[n] = mg;
          it->owned[n] = 1;
        } else {
          it->subs[n] = t->sub[a];  // borrowed; table keeps it alive
          it->owned[n] = 0;
        }
        it->n = ++n;  // keep n consistent for dealloc on error
      } else {
        if (kk == ACT_PLAIN && it->subs[j] == t->sub[a])
          continue;  // same record twice (duplicate filter rows)
        PyObject *mg = PyObject_CallFunctionObjArgs(
            g_merge_fn, it->subs[j], t->sub[a], t->key[a], nullptr);
        if (!mg) return bail();
        if (it->owned[j]) Py_DECREF(it->subs[j]);
        it->subs[j] = mg;
        it->owned[j] = 1;
      }
    }
  }
  // finalize overrides: fold any still-pending base contribution (all
  // of that client's tail rows preceded the base row), drop no-op
  // overrides that resolved back to the base entry, and emit the
  // arrays ascending by slot for the iterator's single-cursor pass
  if (!ovr_build.empty()) {
    for (auto &ob : ovr_build)
      if (!fold_base(ob)) return bail();
    std::sort(ovr_build.begin(), ovr_build.end(),
              [](const OvrBuild &x, const OvrBuild &y) {
                return x.slot < y.slot;
              });
    for (auto &ob : ovr_build) {
      if (ob.cur == base_sub_at(it, ob.slot)) {
        if (ob.owned) Py_DECREF(ob.cur);
        ob.cur = nullptr;
        ob.owned = false;
        continue;  // identical to the base entry: not an override
      }
      if (!ob.owned) Py_INCREF(ob.cur);
      it->ovr_slots[it->n_ovr] = ob.slot;
      it->ovr_subs[it->n_ovr] = ob.cur;
      it->n_ovr++;
      ob.cur = nullptr;  // ref transferred to the intents object
      ob.owned = false;
    }
  }
  // charge the icache at TRUE storage cost (ADVICE r03 discipline):
  // own entries + overrides + a COPIED shared map's pairs. Chains and
  // single-shared-row results that alias immutable per-row structures
  // cost the budget nothing for the aliased part — on $share-heavy
  // corpora this is the difference between ~12K cacheable row sets
  // and several hundred thousand. The floor prices the fixed per-entry
  // overhead (object header + arrays + key bytes + dict slot ≈ 300B ≈
  // 16 pair-equivalents) so tiny chains cannot balloon the dict.
  const Py_ssize_t charge =
      std::max<Py_ssize_t>(n + it->n_ovr + sh_owned_pairs, 16);
  if (t->icache_pairs + charge > kDecodeCachePairsCap) {
    if (t->icache_hits < kClearMinHits &&
        ++t->icache_skips < kAdmissionRetry) {
      Py_DECREF(key);              // cold stream: stop churning
      return reinterpret_cast<PyObject *>(it);
    }
    PyDict_Clear(t->icache);
    t->icache_pairs = 0;
    t->icache_hits = 0;
    t->icache_skips = 0;
  }
  const int rc =
      PyDict_SetItem(t->icache, key, reinterpret_cast<PyObject *>(it));
  Py_DECREF(key);
  if (rc < 0) {
    Py_DECREF(it);
    return nullptr;
  }
  t->icache_pairs += charge;
  return reinterpret_cast<PyObject *>(it);
}

// the shared zero-entry intents for unmatched topics (one per table)
PyObject *empty_intents_for(DecodeTable *t, PyObject *cap) {
  if (!t->empty_intents) {
    auto *it = intents_alloc(cap, 0);
    if (!it) return nullptr;
    t->empty_intents = reinterpret_cast<PyObject *>(it);
  }
  return Py_NewRef(t->empty_intents);
}

// pass 1 of a batch decode — verify (pure C): each candidate (topic,
// row) pair's token window against the row's verify array; survivors
// are appended to (v_tp, v_rw). toks: [B, Wt] tokens in the compact
// dtype (mode 1/2/4 = u8/u16/i32), pad: that dtype's pad value. Topics
// deeper than the tokenizer keeps (|len| 127) are the CPU trie's in
// every path, so none of their pairs survive.
template <typename Row>
void verify_pairs(const DecodeTable *t, const void *toks, int mode,
                  int32_t pad, const int8_t *lens_enc, Py_ssize_t B,
                  Py_ssize_t Wt, const int64_t *ti, const Row *rw,
                  Py_ssize_t N, std::vector<int64_t> &v_tp,
                  std::vector<int32_t> &v_rw) {
  const Py_ssize_t W = t->W < Wt ? t->W : Wt;
  const auto *tok = static_cast<const int32_t *>(t->tok.buf);
  const auto *md = static_cast<const int32_t *>(t->min_depth.buf);
  const auto *fl = static_cast<const uint8_t *>(t->flags.buf);
  for (Py_ssize_t k = 0; k < N; k++) {
    const int64_t tp = ti[k], r = rw[k];
    if (tp < 0 || tp >= B || r < 0 || r >= t->R) continue;
    const uint8_t f = fl[r];
    if (!(f & FLAG_VALID)) continue;
    const int8_t le = lens_enc[tp];
    const int32_t ln = le < 0 ? -static_cast<int32_t>(le) : le;
    if (ln >= 127) continue;
    const int32_t m = md[r];
    if ((f & FLAG_EXACT) ? (ln != m) : (ln < m)) continue;
    if (le < 0 && (f & FLAG_WILDF)) continue;
    const int32_t *rt = tok + r * t->W;
    bool ok = true;
    for (Py_ssize_t i = 0; i < W; i++) {
      const int32_t rv = rt[i];
      if (rv == VER_ANY || rv == VER_PLUS) continue;
      if (rv != topic_tok(toks, mode, pad, tp, Wt, i)) {
        ok = false;
        break;
      }
    }
    // window positions past the topic matrix (t->W > Wt) would read
    // topic token -1; only ANY/'+'/pad-literal can match there
    for (Py_ssize_t i = W; ok && i < t->W; i++) {
      const int32_t rv = rt[i];
      if (rv != VER_ANY && rv != VER_PLUS && rv != -1) ok = false;
    }
    if (!ok) continue;
    v_tp.push_back(tp);
    v_rw.push_back(static_cast<int32_t>(r));
  }
}

// pass 2 — counting-sort the verified pairs by topic (pairs may
// interleave device and host-probe streams), then resolve each topic's
// row SET through the table's result cache: topics overwhelmingly
// repeat a small number of row sets (shallow-'#' buckets), so the
// expensive union runs once per distinct set, not once per topic.
// Returns list[SubscriberSet | DeliveryIntents] of length B, every
// slot populated.
PyObject *union_verified(DecodeTable *t, PyObject *cap, Py_ssize_t B,
                         const std::vector<int64_t> &v_tp,
                         const std::vector<int32_t> &v_rw,
                         const bool intents) {
  PyObject *out = PyList_New(B);
  if (!out) return nullptr;
  for (Py_ssize_t i = 0; i < B; i++)
    PyList_SET_ITEM(out, i, Py_NewRef(Py_None));
  auto bail = [&]() -> PyObject * {
    Py_DECREF(out);
    return nullptr;
  };
  const Py_ssize_t M = (Py_ssize_t)v_tp.size();
  std::vector<int64_t> t_cnt(B + 1, 0);
  for (Py_ssize_t k = 0; k < M; k++) t_cnt[v_tp[k] + 1]++;
  for (Py_ssize_t i = 0; i < B; i++) t_cnt[i + 1] += t_cnt[i];
  std::vector<int32_t> sorted_rw(M);
  {
    std::vector<int64_t> cur(t_cnt.begin(), t_cnt.end() - 1);
    for (Py_ssize_t k = 0; k < M; k++)
      sorted_rw[cur[v_tp[k]]++] = v_rw[k];
  }
  std::vector<int32_t> rowbuf;
  for (Py_ssize_t tp = 0; tp < B; tp++) {
    const int64_t lo = t_cnt[tp], hi = t_cnt[tp + 1];
    if (lo == hi) continue;
    rowbuf.assign(sorted_rw.begin() + lo, sorted_rw.begin() + hi);
    std::sort(rowbuf.begin(), rowbuf.end());
    rowbuf.erase(std::unique(rowbuf.begin(), rowbuf.end()),
                 rowbuf.end());
    PyObject *res =
        intents ? cached_intents_result(t, cap, rowbuf.data(),
                                        (Py_ssize_t)rowbuf.size())
                : cached_rowset_result(t, rowbuf.data(),
                                       (Py_ssize_t)rowbuf.size());
    if (!res) return bail();
    PyList_SetItem(out, tp, res);  // steals; replaces the None
  }
  // fill the untouched slots so every consumer sees a real result
  // object. NOTE: populated slots may be SHARED (cache hits alias one
  // object across topics and calls) — callers must treat results as
  // immutable and deep_copy()/to_set() before mutating
  // (see SigEngine.decode_pairs' contract)
  for (Py_ssize_t i = 0; i < B; i++) {
    if (PyList_GET_ITEM(out, i) != Py_None) continue;
    PyObject *n;
    if (intents) {
      n = empty_intents_for(t, cap);
    } else {
      n = reinterpret_cast<PyObject *>(subset_new_fast(nullptr, nullptr));
    }
    if (!n) return bail();
    PyList_SetItem(out, i, n);
  }
  return out;
}

// decode_batch(table, toks, mode, pad, lens_enc, B, ti, rw)
//   -> list[SubscriberSet] of length B (every slot populated)
//
// ti/rw: int64 UNVERIFIED candidate pair arrays (fallback topics and
// out-of-table rows already dropped by _candidate_pairs). Unverified
// pairs are discarded; verified rows' action streams are applied.
PyObject *decode_batch_impl(PyObject *args, const bool intents) {
  PyObject *cap, *toks_o, *lens_o, *ti_o, *rw_o;
  int mode;
  long pad_l;
  Py_ssize_t B;
  if (!PyArg_ParseTuple(args, "OOilOnOO", &cap, &toks_o, &mode, &pad_l,
                        &lens_o, &B, &ti_o, &rw_o))
    return nullptr;
  auto *t = static_cast<DecodeTable *>(
      PyCapsule_GetPointer(cap, "maxmq_decode.table"));
  if (!t) return nullptr;

  Py_buffer bufs[4];
  PyObject *objs[4] = {toks_o, lens_o, ti_o, rw_o};
  int n_buf = 0;
  struct Rel {
    Py_buffer *b;
    int *n;
    ~Rel() {
      for (int i = 0; i < *n; i++) PyBuffer_Release(&b[i]);
    }
  } rel{bufs, &n_buf};
  while (n_buf < 4) {
    if (PyObject_GetBuffer(objs[n_buf], &bufs[n_buf], PyBUF_SIMPLE) < 0)
      return nullptr;
    n_buf++;
  }
  const Py_buffer &toks = bufs[0], &lens = bufs[1];
  const Py_buffer &ti_b = bufs[2], &rw_b = bufs[3];

  const Py_ssize_t N = ti_b.len / 8;
  const Py_ssize_t Wt = B ? toks.len / (B * mode) : 0;
  if ((Py_ssize_t)rw_b.len / 8 < N || (Py_ssize_t)lens.len < B) {
    PyErr_SetString(PyExc_ValueError, "batch array lengths disagree");
    return nullptr;
  }
  std::vector<int64_t> v_tp;
  std::vector<int32_t> v_rw;
  v_tp.reserve(N);
  v_rw.reserve(N);
  verify_pairs(t, toks.buf, mode, static_cast<int32_t>(pad_l),
               static_cast<const int8_t *>(lens.buf), B, Wt,
               static_cast<const int64_t *>(ti_b.buf),
               static_cast<const int64_t *>(rw_b.buf), N, v_tp, v_rw);
  return union_verified(t, cap, B, v_tp, v_rw, intents);
}

PyObject *decode_batch(PyObject *, PyObject *args) {
  return decode_batch_impl(args, false);
}

// prewarm_bases(capsule, start_row, max_builds) -> next_row.
// Builds the chained-decode anchors (slot map + pinned single-row
// intents) for every row at or above the LIVE runtime base bar,
// starting at start_row, until max_builds rows were built or the
// prewarm budget closes (3/4 of the slot-map cap: the remainder stays
// free for traffic-driven population of rows this row-order sweep
// would otherwise starve on over-budget tables). Returns the row to
// resume from (== the table's row count when finished), so engines can
// populate the anchors in bounded chunks at compile/boot time instead
// of paying the ramp across the first few hundred thousand cold
// topics.
PyObject *prewarm_bases(PyObject *, PyObject *args) {
  PyObject *cap;
  Py_ssize_t start, max_builds;
  if (!PyArg_ParseTuple(args, "Onn", &cap, &start, &max_builds))
    return nullptr;
  auto *t = static_cast<DecodeTable *>(
      PyCapsule_GetPointer(cap, "maxmq_decode.table"));
  if (!t) return nullptr;
  const auto *off = static_cast<const int64_t *>(t->offsets.buf);
  const Py_ssize_t bar =
      g_multi_base ? std::max<Py_ssize_t>(16, g_chain_min_base / 4)
                   : g_chain_min_base;
  Py_ssize_t built = 0;
  Py_ssize_t r = start < 0 ? 0 : start;
  for (; r < t->R && built < max_builds; r++) {
    const Py_ssize_t p = (off[r + 1] - off[r]) - t->shcount[r];
    if (p < bar) continue;
    // anchor-eligible $share rows: prebuild the per-row shared map
    // too (same first-touch class, same eligibility bar — sub-bar
    // rows keep building theirs lazily on first touch)
    if (t->shcount[r] && !t->rshared[r]) {
      if (!row_shared(t, r)) return nullptr;
      built++;
    }
    if (t->row_slot.count(static_cast<int32_t>(r))) continue;
    if (t->slot_entries + p > g_slot_map_cap / 4 * 3) {
      continue;                  // over-budget ROW, not a closed sweep:
                                 // smaller later rows may still fit
                                 // (the skip is one hash probe, so a
                                 // fully-spent budget costs ms of scan
                                 // once, bounded by R)
    }
    PyObject *b = nullptr;
    auto *m = ensure_row_base(t, cap, static_cast<int32_t>(r), p, &b);
    if (!m) continue;            // hard-cap decline for THIS row only
    if (!b) return nullptr;      // python error from the base build
    Py_DECREF(b);
    built++;
  }
  return PyLong_FromSsize_t(r);
}

PyObject *decode_batch_intents(PyObject *, PyObject *args) {
  return decode_batch_impl(args, true);
}

// ----------------------------------------------------------------- //
//  Device-free host match: one call per batch                       //
// ----------------------------------------------------------------- //

// The host probes live in the ctypes runtime (native/maxmq_native.cpp);
// this extension calls its C entry points through the addresses
// Python resolved once per compiled snapshot, on the handles that
// runtime built for the snapshot.
using TokenizeProbeFn = int64_t (*)(void *, void *, const char *,
                                    int64_t, int64_t, int64_t, int32_t,
                                    void *, int8_t *, int64_t *,
                                    int32_t *, int64_t);
using ProbeRunFn = int64_t (*)(void *, const void *, int32_t,
                               const int8_t *, int64_t, int64_t,
                               int64_t *, int32_t *, int64_t, int32_t);

struct HostProbe {
  TokenizeProbeFn tokenize_probe;  // mq_tokenize_probe
  ProbeRunFn probe_run;            // mq_probe_run
  void *vocab;                     // the snapshot's Vocab
  void *probe;                     // exact + '+' groups
  void *hash;                      // '#' groups (depth >=); null if none
  int64_t window;                  // token window (the verify width)
  int32_t mode, pad;               // compact token dtype: 1/2/4, pad
  PyObject *owners;                // the handles' Python owners
};

void host_destroy(PyObject *capsule) {
  auto *h = static_cast<HostProbe *>(
      PyCapsule_GetPointer(capsule, "maxmq_decode.host"));
  if (!h) return;
  Py_XDECREF(h->owners);
  delete h;
}

// host_new(tokenize_probe, probe_run, vocab, probe, hash, window, mode,
//          pad, owners) -> capsule. The first five are addresses (hash
// may be 0); owners is held for the capsule's life, so the handles it
// owns outlive every call.
PyObject *host_new(PyObject *, PyObject *args) {
  PyObject *fn_tp, *fn_pr, *vocab, *probe, *hash, *owners;
  Py_ssize_t window;
  int mode, pad;
  if (!PyArg_ParseTuple(args, "OOOOOniiO", &fn_tp, &fn_pr, &vocab, &probe,
                        &hash, &window, &mode, &pad, &owners))
    return nullptr;
  void *ptr[5];
  PyObject *objs[5] = {fn_tp, fn_pr, vocab, probe, hash};
  for (int i = 0; i < 5; i++) {
    ptr[i] = PyLong_AsVoidPtr(objs[i]);
    if (!ptr[i] && PyErr_Occurred()) return nullptr;
  }
  if (!ptr[0] || !ptr[1] || !ptr[2] || !ptr[3] || window < 1 ||
      (mode != 1 && mode != 2 && mode != 4)) {
    PyErr_SetString(PyExc_ValueError, "host probe handles incomplete");
    return nullptr;
  }
  auto *h = new HostProbe{reinterpret_cast<TokenizeProbeFn>(ptr[0]),
                          reinterpret_cast<ProbeRunFn>(ptr[1]),
                          ptr[2], ptr[3], ptr[4], window, mode, pad,
                          Py_NewRef(owners)};
  PyObject *cap = PyCapsule_New(h, "maxmq_decode.host", host_destroy);
  if (!cap) {
    Py_DECREF(h->owners);
    delete h;
  }
  return cap;
}

// a probe entry point's (topic, row) hits, grown until they fit: the
// entry points return the total and write at most `cap` pairs
template <typename Run>
int64_t probe_hits(std::vector<int64_t> &ti, std::vector<int32_t> &rw,
                   int64_t cap, Run &&run) {
  for (;;) {
    ti.resize(cap);
    rw.resize(cap);
    const int64_t total = run(ti.data(), rw.data(), cap);
    if (total <= cap) return total;
    cap = total;
  }
}

constexpr long HOST_FORM_SET = 0;      // list[SubscriberSet]
constexpr long HOST_FORM_INTENTS = 1;  // list[DeliveryIntents]
constexpr long HOST_FORM_PAIRS = 2;    // (topic ids, row ids), verified

// host_batch(table, host, topics, form, mark) -> (results, fallbacks)
//
// The whole device-free match of one batch in one call: tokenize +
// exact/'+' probe, the '#'-group probe, then pair assembly + verify +
// the entry union of decode_batch(_intents). `fallbacks` is the tuple
// of topic indices too deep for the tokenizer (answered by the CPU
// trie; their slots hold empty results). The PAIRS form returns the
// verified pairs as two lists instead of unioning them (an overlay
// window's union filters removed subscriptions in Python). `mark` is
// None, or a callable that is called with 0, 1 and 2 as each of the
// three parts begins (the caller's batch record times them); the
// caller ends the last.
PyObject *host_batch(PyObject *, PyObject *const *args, Py_ssize_t nargs) {
  if (nargs != 5) {
    PyErr_SetString(PyExc_TypeError, "host_batch takes 5 arguments");
    return nullptr;
  }
  PyObject *cap = args[0];
  auto *t = static_cast<DecodeTable *>(
      PyCapsule_GetPointer(cap, "maxmq_decode.table"));
  if (!t) return nullptr;
  auto *h = static_cast<HostProbe *>(
      PyCapsule_GetPointer(args[1], "maxmq_decode.host"));
  if (!h) return nullptr;
  PyObject *topics = args[2];
  if (!PyList_Check(topics)) {
    PyErr_SetString(PyExc_TypeError, "topics must be a list");
    return nullptr;
  }
  const long form = PyLong_AsLong(args[3]);
  if (form == -1 && PyErr_Occurred()) return nullptr;
  PyObject *mark = args[4] == Py_None ? nullptr : args[4];
  auto begin_part = [mark](long k) -> bool {
    if (!mark) return true;
    PyObject *n = PyLong_FromLong(k);
    if (!n) return false;
    PyObject *r = PyObject_CallOneArg(mark, n);
    Py_DECREF(n);
    Py_XDECREF(r);
    return r != nullptr;
  };
  if (!begin_part(0)) return nullptr;

  // the topics' utf-8 bytes, '\0'-joined as the tokenizer splits them
  // (U+0000 cannot appear in a topic name [MQTT-1.5.4-2])
  const Py_ssize_t B = PyList_GET_SIZE(topics);
  std::string buf;
  for (Py_ssize_t i = 0; i < B; i++) {
    PyObject *s = PyList_GET_ITEM(topics, i);
    if (!PyUnicode_Check(s)) {
      PyErr_SetString(PyExc_TypeError, "topics must be str");
      return nullptr;
    }
    Py_ssize_t n;
    const char *u = PyUnicode_AsUTF8AndSize(s, &n);
    if (!u) return nullptr;
    if (i) buf.push_back('\0');
    buf.append(u, n);
  }

  const int64_t W = h->window;
  std::vector<uint8_t> toks(static_cast<size_t>(B * W * h->mode));
  std::vector<int8_t> lens(B);
  std::vector<int64_t> ti, ti_h;
  std::vector<int32_t> rw, rw_h;
  const int64_t first_cap = 4 * B + 16;
  const int64_t n_hits =
      B ? probe_hits(ti, rw, first_cap,
                     [&](int64_t *o_ti, int32_t *o_rw, int64_t c) {
                       return h->tokenize_probe(
                           h->vocab, h->probe, buf.data(),
                           static_cast<int64_t>(buf.size()), B, W,
                           h->mode, toks.data(), lens.data(), o_ti, o_rw,
                           c);
                     })
        : 0;
  if (!begin_part(1)) return nullptr;
  const int64_t n_hash =
      B && h->hash
          ? probe_hits(ti_h, rw_h, first_cap,
                       [&](int64_t *o_ti, int32_t *o_rw, int64_t c) {
                         return h->probe_run(h->hash, toks.data(),
                                             h->mode, lens.data(), B, W,
                                             o_ti, o_rw, c, 0);
                       })
          : 0;
  if (!begin_part(2)) return nullptr;

  Py_ssize_t n_fall = 0;
  for (Py_ssize_t i = 0; i < B; i++)
    if (lens[i] == 127 || lens[i] == -127) n_fall++;
  PyObject *fall = PyTuple_New(n_fall);
  if (!fall) return nullptr;
  for (Py_ssize_t i = 0, k = 0; k < n_fall; i++) {
    if (lens[i] != 127 && lens[i] != -127) continue;
    PyObject *v = PyLong_FromSsize_t(i);
    if (!v) {
      Py_DECREF(fall);
      return nullptr;
    }
    PyTuple_SET_ITEM(fall, k++, v);
  }

  std::vector<int64_t> v_tp;
  std::vector<int32_t> v_rw;
  v_tp.reserve(n_hits + n_hash);
  v_rw.reserve(n_hits + n_hash);
  verify_pairs(t, toks.data(), h->mode, h->pad, lens.data(), B, W,
               ti.data(), rw.data(), n_hits, v_tp, v_rw);
  verify_pairs(t, toks.data(), h->mode, h->pad, lens.data(), B, W,
               ti_h.data(), rw_h.data(), n_hash, v_tp, v_rw);
  PyObject *out;
  if (form == HOST_FORM_PAIRS) {
    const Py_ssize_t M = static_cast<Py_ssize_t>(v_tp.size());
    PyObject *tl = PyList_New(M), *rl = PyList_New(M);
    out = tl && rl ? PyTuple_Pack(2, tl, rl) : nullptr;
    for (Py_ssize_t k = 0; out && k < M; k++) {
      PyObject *a = PyLong_FromLongLong(v_tp[k]);
      PyObject *b = PyLong_FromLong(v_rw[k]);
      if (!a || !b) {
        Py_XDECREF(a);
        Py_XDECREF(b);
        Py_CLEAR(out);
        break;
      }
      PyList_SET_ITEM(tl, k, a);
      PyList_SET_ITEM(rl, k, b);
    }
    Py_XDECREF(tl);
    Py_XDECREF(rl);
  } else {
    out = union_verified(t, cap, B, v_tp, v_rw, form == HOST_FORM_INTENTS);
  }
  if (!out) {
    Py_DECREF(fall);
    return nullptr;
  }
  PyObject *ret = PyTuple_Pack(2, out, fall);
  Py_DECREF(out);
  Py_DECREF(fall);
  return ret;
}

PyObject *set_slot_map_cap(PyObject *, PyObject *arg) {
  const Py_ssize_t v = PyLong_AsSsize_t(arg);
  if (v == -1 && PyErr_Occurred()) return nullptr;
  if (v < 1) {
    PyErr_SetString(PyExc_ValueError, "slot map cap must be positive");
    return nullptr;
  }
  g_slot_map_cap = v;
  Py_RETURN_NONE;
}

PyObject *get_slot_map_cap(PyObject *, PyObject *) {
  return PyLong_FromSsize_t(g_slot_map_cap);
}

// _slot_map_stats(capsule) -> (rows_with_slot_maps, slot_entries):
// observability for the chained-decode anchor budget (metrics + the
// prewarm tests assert population through it).
PyObject *slot_map_stats(PyObject *, PyObject *arg) {
  auto *t = static_cast<DecodeTable *>(
      PyCapsule_GetPointer(arg, "maxmq_decode.table"));
  if (!t) return nullptr;
  return Py_BuildValue("(nn)",
                       static_cast<Py_ssize_t>(t->row_slot.size()),
                       t->slot_entries);
}

// ADR 019: the per-subscriber PUBLISH frame head — fixed-header flags
// byte, remaining-length varint, topic segment, optional packet id,
// optional property-length varint. The one fresh allocation a patched
// template delivery makes; must stay byte-identical to the Python
// builder in protocol/wire.py (_encode_head_py), which the
// differential tests pin. props_len < 0 means a v3 frame (no
// properties block); tail_len is the payload byte count following the
// head and properties on the wire.
inline int head_varint(uint8_t *dst, Py_ssize_t v) {
  int n = 0;
  do {
    uint8_t b = static_cast<uint8_t>(v & 0x7f);
    v >>= 7;
    if (v) b |= 0x80;
    dst[n++] = b;
  } while (v);
  return n;
}

PyObject *encode_publish_template(PyObject *, PyObject *args) {
  int flags;
  Py_buffer topic;
  Py_ssize_t packet_id, props_len, tail_len;
  if (!PyArg_ParseTuple(args, "iy*nnn", &flags, &topic, &packet_id,
                        &props_len, &tail_len))
    return nullptr;
  Py_ssize_t remaining = topic.len + (packet_id ? 2 : 0) + tail_len;
  uint8_t pbuf[5];
  int pn = 0;
  if (props_len >= 0) {
    pn = head_varint(pbuf, props_len);
    remaining += pn + props_len;
  }
  if (remaining > 268435455) {  // varint ceiling [MQTT-2.2.3]
    PyBuffer_Release(&topic);
    PyErr_SetString(PyExc_ValueError, "frame exceeds varint ceiling");
    return nullptr;
  }
  uint8_t rbuf[5];
  const int rn = head_varint(rbuf, remaining);
  const Py_ssize_t total =
      1 + rn + topic.len + (packet_id ? 2 : 0) + pn;
  PyObject *out = PyBytes_FromStringAndSize(nullptr, total);
  if (!out) {
    PyBuffer_Release(&topic);
    return nullptr;
  }
  auto *w =
      reinterpret_cast<uint8_t *>(PyBytes_AS_STRING(out));
  *w++ = static_cast<uint8_t>(flags);
  std::memcpy(w, rbuf, rn);
  w += rn;
  std::memcpy(w, topic.buf, topic.len);
  w += topic.len;
  if (packet_id) {
    *w++ = static_cast<uint8_t>((packet_id >> 8) & 0xff);
    *w++ = static_cast<uint8_t>(packet_id & 0xff);
  }
  std::memcpy(w, pbuf, pn);
  PyBuffer_Release(&topic);
  return out;
}

PyMethodDef methods[] = {
    {"configure", configure, METH_VARARGS,
     "Register merge_subscription and the Subscription copy helper."},
    {"table_new", table_new, METH_VARARGS,
     "Register a compiled-snapshot decode table; returns a capsule."},
    {"decode_batch", decode_batch, METH_VARARGS,
     "Verify candidate pairs and union their subscriber entries into "
     "per-topic SubscriberSets."},
    {"decode_batch_intents", decode_batch_intents, METH_VARARGS,
     "Verify candidate pairs and union their subscriber entries into "
     "per-topic DeliveryIntents (the fan-out hot-path form)."},
    {"host_new", host_new, METH_VARARGS,
     "Bind a compiled snapshot's host probe handles (the ctypes "
     "runtime's entry points and tables); returns a capsule."},
    {"host_batch", reinterpret_cast<PyCFunction>(host_batch),
     METH_FASTCALL,
     "The device-free match of one batch: tokenize, the exact/'+' and "
     "'#' probes, verify and union, in one call."},
    {"table_release", table_release, METH_O,
     "Drop a snapshot table's caches, breaking the intents->capsule "
     "reference cycle (call when the snapshot is dropped)."},
    {"_set_chain_enabled", set_chain_enabled, METH_O,
     "TEST ONLY: disable/enable the chained-union fast path so the "
     "suite can A/B chained vs full unions of the same row sets."},
    {"prewarm_bases", prewarm_bases, METH_VARARGS,
     "Build chained-decode row anchors in bounded chunks "
     "(capsule, start_row, max_builds) -> next_row."},
    {"_set_multi_base", set_multi_base, METH_O,
     "TEST/TUNING: enable/disable multi-row base composition (off = "
     "legacy single-fattest-row chaining)."},
    {"_set_chain_params", set_chain_params, METH_VARARGS,
     "TEST/TUNING: (min_base, tail_num, tail_den) — chain when the "
     "fattest row has >= min_base plain entries and tail <= "
     "fat*tail_num/tail_den."},
    {"_get_chain_params", get_chain_params, METH_NOARGS,
     "The live (min_base, tail_num, tail_den) — so A/B harnesses and "
     "test finally blocks restore the values actually in effect."},
    {"_set_slot_map_cap", set_slot_map_cap, METH_O,
     "TEST ONLY: shrink the per-table slot-map entry budget so the "
     "prewarm budget paths are exercisable at test scale."},
    {"_get_slot_map_cap", get_slot_map_cap, METH_NOARGS,
     "The live slot-map entry budget — restore the saved value, not a "
     "hardcoded default."},
    {"_slot_map_stats", slot_map_stats, METH_O,
     "(rows_with_slot_maps, slot_entries) for a table capsule — "
     "chained-decode anchor population observability."},
    {"encode_publish_template", encode_publish_template, METH_VARARGS,
     "Assemble one subscriber's PUBLISH frame head (ADR 019): "
     "(flags, topic_seg, packet_id, props_len, tail_len) -> bytes."},
    {nullptr, nullptr, 0, nullptr}};

PyModuleDef mod = {PyModuleDef_HEAD_INIT, "maxmq_decode",
                   "Native verify + subscriber-union decode.", -1,
                   methods, nullptr, nullptr, nullptr, nullptr};

}  // namespace

PyMODINIT_FUNC PyInit_maxmq_decode(void) {
  PyObject *m = PyModule_Create(&mod);
  if (!m) return nullptr;
  auto *tp = reinterpret_cast<PyTypeObject *>(
      PyType_FromSpec(&subset_spec));
  if (!tp || PyModule_AddObject(m, "SubscriberSet",
                                reinterpret_cast<PyObject *>(tp)) < 0) {
    Py_XDECREF(reinterpret_cast<PyObject *>(tp));
    Py_DECREF(m);
    return nullptr;
  }
  g_subset_type = tp;  // module holds the ref
  auto *ip = reinterpret_cast<PyTypeObject *>(
      PyType_FromSpec(&intents_spec));
  if (!ip || PyModule_AddObject(m, "DeliveryIntents",
                                reinterpret_cast<PyObject *>(ip)) < 0) {
    Py_XDECREF(reinterpret_cast<PyObject *>(ip));
    Py_DECREF(m);
    return nullptr;
  }
  g_intents_type = ip;
  auto *itp = reinterpret_cast<PyTypeObject *>(
      PyType_FromSpec(&intents_iter_spec));
  if (!itp) {
    Py_DECREF(m);
    return nullptr;
  }
  g_intents_iter_type = itp;  // not exposed; module keeps the ref alive
  return m;
}
