// Native round converter: a run of Change objects -> one AMW1 frame.
//
// The same bytes as sync/frames.py `columns_to_bytes(changes_to_columns(run))`,
// made in one pass over the objects' slots instead of per-op Python. It reads
// `actor`, `seq`, `deps`, `message`, `ops` of each Change and `action`, `obj`,
// `key`, `value`, `elem` of each Op straight from their __slots__ (the slot
// offsets are taken once from the classes' member descriptors), interns the
// strings in the Python path's first-meet order, and returns the frame
// together with the five string tables as lists of the caller's own str
// objects, so nothing is decoded back.
//
// It answers None for anything the Python path might treat differently: a
// Change or Op of another class, a field of a type that is not exactly the
// plain one, an integer past int32 where the frame stores int32, an unset
// slot, a string that does not encode as strict UTF-8 (lone surrogates: the
// Python path writes them with `surrogatepass`). The caller then converts in
// Python, which is the reference this converter is tested against.
//
// Called through ctypes.PyDLL, so the GIL is held throughout. No Python code
// runs during the walk: nothing it allocates is a container the collector
// tracks, until the result lists at the end, and every table string is held
// by a reference of its own from the moment it is interned.

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <structmember.h>

#include <cstdint>
#include <cstring>
#include <string_view>
#include <vector>

namespace {

// value tags, as native/wire.py V_*
enum VTag : int8_t { V_NONE = 0, V_NULL = 1, V_FALSE = 2, V_TRUE = 3,
                     V_INT = 4, V_DOUBLE = 5, V_STR = 6, V_BIGINT = 7 };

constexpr int kActions = 8;   // storage._ACTIONS
constexpr int kSet = 4, kLink = 6, kMove = 7;

PyTypeObject* g_change = nullptr;
PyTypeObject* g_op = nullptr;
PyObject* g_actions[kActions] = {};
Py_ssize_t c_actor, c_seq, c_deps, c_message, c_ops;
Py_ssize_t o_action, o_obj, o_key, o_value, o_elem;

inline PyObject* slot(PyObject* obj, Py_ssize_t off) {
  return *reinterpret_cast<PyObject**>(reinterpret_cast<char*>(obj) + off);
}

// A frame-local string table: first-meet order, one owned reference a
// string. Looked up by the str's own (cached) hash in an open-addressed
// index; an equal str is the same string, whatever object carries it.
struct Table {
  std::vector<PyObject*> items;
  std::vector<Py_hash_t> hashes;
  std::vector<std::string_view> utf8;   // into the items' UTF-8 buffers
  std::vector<int32_t> index;           // -1 = empty; a power of two long
  size_t blob = 0;

  ~Table() {
    for (PyObject* s : items) Py_DECREF(s);
  }

  void rehash(size_t cap) {
    index.assign(cap, -1);
    for (size_t id = 0; id < items.size(); ++id) {
      size_t i = static_cast<size_t>(hashes[id]) & (cap - 1);
      while (index[i] >= 0) i = (i + 1) & (cap - 1);
      index[i] = static_cast<int32_t>(id);
    }
  }

  // -1 where the string does not encode strictly (error cleared)
  int32_t add(PyObject* s) {
    Py_hash_t h = PyObject_Hash(s);
    if (h == -1) {
      PyErr_Clear();
      return -1;
    }
    if (index.empty()) rehash(64);
    size_t mask = index.size() - 1;
    size_t i = static_cast<size_t>(h) & mask;
    for (int32_t id; (id = index[i]) >= 0; i = (i + 1) & mask) {
      if (hashes[id] == h &&
          (items[id] == s || PyUnicode_Compare(items[id], s) == 0))
        return id;
    }
    Py_ssize_t n;
    const char* p = PyUnicode_AsUTF8AndSize(s, &n);
    if (p == nullptr) {
      PyErr_Clear();
      return -1;
    }
    int32_t id = static_cast<int32_t>(items.size());
    Py_INCREF(s);
    items.push_back(s);
    hashes.push_back(h);
    utf8.emplace_back(p, static_cast<size_t>(n));
    blob += static_cast<size_t>(n);
    index[i] = id;
    if (2 * items.size() > index.size()) rehash(2 * index.size());
    return id;
  }

  // a new list of the table's strings (the references move into it)
  PyObject* take() {
    PyObject* out = PyList_New(static_cast<Py_ssize_t>(items.size()));
    if (out == nullptr) return nullptr;
    for (size_t i = 0; i < items.size(); ++i)
      PyList_SET_ITEM(out, static_cast<Py_ssize_t>(i), items[i]);
    items.clear();
    return out;
  }
};

inline bool as_i32(PyObject* v, int32_t* out) {
  if (!PyLong_CheckExact(v)) return false;
  int overflow = 0;
  long long x = PyLong_AsLongLongAndOverflow(v, &overflow);
  if (overflow != 0 || x < INT32_MIN || x > INT32_MAX) return false;
  *out = static_cast<int32_t>(x);
  return true;
}

struct Columns {
  std::vector<int32_t> change_actor, change_seq, change_msg;
  std::vector<int32_t> deps_off{0}, deps_actor, deps_seq, op_off{0};
  std::vector<int8_t> op_action, op_vtag;
  std::vector<int32_t> op_obj, op_key, op_elem, op_vstr;
  std::vector<int64_t> op_vint;
  std::vector<double> op_vdbl;
  Table actors, objects, keys, messages, strings;
};

bool op_row(PyObject* op, Columns& c) {
  if (Py_TYPE(op) != g_op) return false;
  PyObject* action = slot(op, o_action);
  PyObject* obj = slot(op, o_obj);
  PyObject* key = slot(op, o_key);
  PyObject* elem = slot(op, o_elem);
  if (action == nullptr || obj == nullptr || key == nullptr ||
      elem == nullptr || !PyUnicode_CheckExact(action) ||
      !PyUnicode_CheckExact(obj))
    return false;
  int a = 0;
  while (a < kActions && g_actions[a] != action) ++a;
  if (a == kActions) {   // an equal str that is not the interned constant
    a = 0;
    while (a < kActions && PyUnicode_Compare(g_actions[a], action) != 0) ++a;
    if (a == kActions) return false;
  }
  int32_t obj_i = c.objects.add(obj);
  if (obj_i < 0) return false;
  int32_t key_i = -1;
  if (key != Py_None) {
    if (!PyUnicode_CheckExact(key) || (key_i = c.keys.add(key)) < 0)
      return false;
  }
  int32_t elem_i = -1;
  if (elem != Py_None && !as_i32(elem, &elem_i)) return false;

  int8_t tag = V_NONE;
  int64_t vint = 0;
  double vdbl = 0.0;
  int32_t vstr = -1;
  if (a == kSet || a == kLink || a == kMove) {
    PyObject* v = slot(op, o_value);
    if (v == nullptr) return false;
    if (v == Py_None) {
      tag = V_NULL;
    } else if (v == Py_True) {
      tag = V_TRUE;
    } else if (v == Py_False) {
      tag = V_FALSE;
    } else if (PyLong_CheckExact(v)) {
      int overflow = 0;
      long long x = PyLong_AsLongLongAndOverflow(v, &overflow);
      if (overflow == 0) {
        tag = V_INT;
        vint = x;
      } else {   // past int64: its decimal text in the strings table
        PyObject* text = PyObject_Str(v);
        if (text == nullptr) {
          PyErr_Clear();
          return false;
        }
        vstr = c.strings.add(text);
        Py_DECREF(text);   // the table holds its own reference
        if (vstr < 0) return false;
        tag = V_BIGINT;
      }
    } else if (PyFloat_CheckExact(v)) {
      tag = V_DOUBLE;
      vdbl = PyFloat_AS_DOUBLE(v);
    } else if (PyUnicode_CheckExact(v)) {
      if ((vstr = c.strings.add(v)) < 0) return false;
      tag = V_STR;
    } else {
      return false;
    }
  }
  c.op_action.push_back(static_cast<int8_t>(a));
  c.op_obj.push_back(obj_i);
  c.op_key.push_back(key_i);
  c.op_elem.push_back(elem_i);
  c.op_vtag.push_back(tag);
  c.op_vint.push_back(vint);
  c.op_vdbl.push_back(vdbl);
  c.op_vstr.push_back(vstr);
  return true;
}

bool change_row(PyObject* ch, Columns& c) {
  if (Py_TYPE(ch) != g_change) return false;
  PyObject* actor = slot(ch, c_actor);
  PyObject* seq = slot(ch, c_seq);
  PyObject* deps = slot(ch, c_deps);
  PyObject* message = slot(ch, c_message);
  PyObject* ops = slot(ch, c_ops);
  if (actor == nullptr || seq == nullptr || deps == nullptr ||
      message == nullptr || ops == nullptr || !PyUnicode_CheckExact(actor) ||
      !PyDict_CheckExact(deps) ||
      !(PyTuple_CheckExact(ops) || PyList_CheckExact(ops)))
    return false;
  int32_t actor_i = c.actors.add(actor);
  int32_t seq_i;
  if (actor_i < 0 || !as_i32(seq, &seq_i)) return false;
  int32_t msg_i = -1;
  if (message != Py_None) {
    if (!PyUnicode_CheckExact(message) || (msg_i = c.messages.add(message)) < 0)
      return false;
  }
  c.change_actor.push_back(actor_i);
  c.change_seq.push_back(seq_i);
  c.change_msg.push_back(msg_i);

  Py_ssize_t pos = 0;
  PyObject *k, *v;
  while (PyDict_Next(deps, &pos, &k, &v)) {
    int32_t dep_seq;
    if (!PyUnicode_CheckExact(k) || !as_i32(v, &dep_seq)) return false;
    int32_t dep_actor = c.actors.add(k);
    if (dep_actor < 0) return false;
    c.deps_actor.push_back(dep_actor);
    c.deps_seq.push_back(dep_seq);
  }
  c.deps_off.push_back(static_cast<int32_t>(c.deps_actor.size()));

  Py_ssize_t n = PySequence_Fast_GET_SIZE(ops);
  PyObject** items = PySequence_Fast_ITEMS(ops);
  for (Py_ssize_t j = 0; j < n; ++j)
    if (!op_row(items[j], c)) return false;
  c.op_off.push_back(static_cast<int32_t>(c.op_action.size()));
  return true;
}

template <typename T>
char* put(char* p, const std::vector<T>& v) {
  size_t n = v.size() * sizeof(T);
  if (n) std::memcpy(p, v.data(), n);
  return p + n;
}

char* put_u32(char* p, size_t x) {
  uint32_t v = static_cast<uint32_t>(x);
  std::memcpy(p, &v, 4);
  return p + 4;
}

char* put_table(char* p, const Table& t) {
  int32_t off = 0;
  std::memcpy(p, &off, 4);
  p += 4;
  for (const std::string_view& s : t.utf8) {
    off += static_cast<int32_t>(s.size());
    std::memcpy(p, &off, 4);
    p += 4;
  }
  for (const std::string_view& s : t.utf8) {
    if (!s.empty()) std::memcpy(p, s.data(), s.size());
    p += s.size();
  }
  return p;
}

PyObject* frame_of(Columns& c) {
  Table* tables[5] = {&c.actors, &c.objects, &c.keys, &c.messages, &c.strings};
  size_t n_changes = c.change_actor.size(), n_ops = c.op_action.size();
  size_t n_deps = c.deps_actor.size();
  size_t total = 4 + 32 + 4 * (3 * n_changes + 2 * (n_changes + 1)) +
                 8 * n_deps + n_ops * (1 + 4 + 4 + 4 + 1 + 8 + 8 + 4);
  for (Table* t : tables) {
    if (t->blob > static_cast<size_t>(INT32_MAX)) return nullptr;
    total += 4 * (t->items.size() + 1) + t->blob;
  }
  PyObject* out = PyBytes_FromStringAndSize(nullptr,
                                            static_cast<Py_ssize_t>(total));
  if (out == nullptr) return nullptr;
  char* p = PyBytes_AS_STRING(out);
  std::memcpy(p, "AMW1", 4);
  p += 4;
  for (size_t n : {n_changes, n_ops, n_deps})
    p = put_u32(p, n);
  for (Table* t : tables) p = put_u32(p, t->items.size());
  p = put(p, c.change_actor);
  p = put(p, c.change_seq);
  p = put(p, c.change_msg);
  p = put(p, c.deps_off);
  p = put(p, c.deps_actor);
  p = put(p, c.deps_seq);
  p = put(p, c.op_off);
  p = put(p, c.op_action);
  p = put(p, c.op_obj);
  p = put(p, c.op_key);
  p = put(p, c.op_elem);
  p = put(p, c.op_vtag);
  p = put(p, c.op_vint);
  p = put(p, c.op_vdbl);
  p = put(p, c.op_vstr);
  for (Table* t : tables) p = put_table(p, *t);
  return out;
}

bool member_offset(PyTypeObject* type, const char* name, Py_ssize_t* off) {
  PyObject* d = PyObject_GetAttrString(reinterpret_cast<PyObject*>(type),
                                       name);
  if (d == nullptr) {
    PyErr_Clear();
    return false;
  }
  bool ok = Py_IS_TYPE(d, &PyMemberDescr_Type) &&
            reinterpret_cast<PyMemberDescrObject*>(d)->d_member->type ==
                T_OBJECT_EX;
  if (ok) *off = reinterpret_cast<PyMemberDescrObject*>(d)->d_member->offset;
  Py_DECREF(d);
  return ok;
}

PyObject* none() {
  PyErr_Clear();
  Py_RETURN_NONE;
}

}  // namespace

extern "C" {

// Bind the converter to the Change and Op classes (their slot offsets) and
// to the action names in storage._ACTIONS order; 0 on success.
int amtpu_frame_init(PyObject* change_type, PyObject* op_type,
                     PyObject* actions) {
  if (!PyType_Check(change_type) || !PyType_Check(op_type) ||
      !PyTuple_CheckExact(actions) || PyTuple_GET_SIZE(actions) != kActions)
    return 1;
  auto* ct = reinterpret_cast<PyTypeObject*>(change_type);
  auto* ot = reinterpret_cast<PyTypeObject*>(op_type);
  if (!member_offset(ct, "actor", &c_actor) ||
      !member_offset(ct, "seq", &c_seq) ||
      !member_offset(ct, "deps", &c_deps) ||
      !member_offset(ct, "message", &c_message) ||
      !member_offset(ct, "ops", &c_ops) ||
      !member_offset(ot, "action", &o_action) ||
      !member_offset(ot, "obj", &o_obj) ||
      !member_offset(ot, "key", &o_key) ||
      !member_offset(ot, "value", &o_value) ||
      !member_offset(ot, "elem", &o_elem))
    return 2;
  for (int a = 0; a < kActions; ++a) {
    PyObject* s = PyTuple_GET_ITEM(actions, a);
    if (!PyUnicode_CheckExact(s)) return 3;
  }
  for (int a = 0; a < kActions; ++a) {
    Py_XDECREF(g_actions[a]);
    g_actions[a] = PyTuple_GET_ITEM(actions, a);
    Py_INCREF(g_actions[a]);
  }
  Py_INCREF(change_type);
  Py_INCREF(op_type);
  Py_XDECREF(reinterpret_cast<PyObject*>(g_change));
  Py_XDECREF(reinterpret_cast<PyObject*>(g_op));
  g_change = ct;
  g_op = ot;
  return 0;
}

// (frame bytes, (actors, objects, keys, messages, strings)) for a list or
// tuple of Change objects, or None where the Python path must convert.
PyObject* amtpu_changes_frame(PyObject* changes) {
  if (g_change == nullptr ||
      !(PyList_CheckExact(changes) || PyTuple_CheckExact(changes)))
    return none();
  Columns c;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(changes);
  c.change_actor.reserve(n);
  c.change_seq.reserve(n);
  c.change_msg.reserve(n);
  c.deps_off.reserve(n + 1);
  c.op_off.reserve(n + 1);
  PyObject** items = PySequence_Fast_ITEMS(changes);
  bool ok = true;
  for (Py_ssize_t i = 0; ok && i < n; ++i) ok = change_row(items[i], c);
  if (!ok) return none();
  PyObject* frame = frame_of(c);
  if (frame == nullptr) return none();
  PyObject* lists = PyTuple_New(5);
  if (lists == nullptr) {
    Py_DECREF(frame);
    return none();
  }
  Table* tables[5] = {&c.actors, &c.objects, &c.keys, &c.messages, &c.strings};
  for (int t = 0; t < 5; ++t) {
    PyObject* l = tables[t]->take();
    if (l == nullptr) {
      Py_DECREF(lists);
      Py_DECREF(frame);
      return none();
    }
    PyTuple_SET_ITEM(lists, t, l);
  }
  PyObject* out = PyTuple_Pack(2, frame, lists);
  Py_DECREF(frame);
  Py_DECREF(lists);
  if (out == nullptr) return none();
  return out;
}

}  // extern "C"
