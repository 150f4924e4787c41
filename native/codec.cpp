// Native WorldQL wire codec: hand-rolled FlatBuffers reader/writer for
// the fixed WorldQLFB schema (reference: worldql_server/src/flatbuffers/
// WorldQLFB_generated.rs; Python twin: worldql_server_tpu/protocol/codec.py).
//
// The reader treats input as untrusted: every load is bounds-checked
// against the buffer (the Rust reference relies on flatbuffers verifier
// semantics; the Python twin bounds-checks likewise). The writer emits
// canonical back-to-front FlatBuffers with per-table vtables (no dedup —
// slightly larger buffers, identical semantics).
//
// C ABI (ctypes consumer: worldql_server_tpu/protocol/native_codec.py):
//   wql_decode(buf, len, WqlMsg* out) -> 0 ok / negative error
//   wql_encode(const WqlMsg* in, uint8_t** out, size_t* out_len) -> 0 ok
//   wql_buffer_free(uint8_t*)
// Strings/bytes in WqlMsg are (pointer, length) views; on decode they
// point into the caller's input buffer (zero-copy).

#include <algorithm>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <vector>

constexpr int32_t WQL_MAX_OBJS = 1024;  // per-message record/entity cap

typedef struct {
  const uint8_t* uuid;  int32_t uuid_len;
  const uint8_t* world; int32_t world_len;
  const uint8_t* data;  int32_t data_len;   // data == NULL → absent
  const uint8_t* flex;  int32_t flex_len;   // flex == NULL → absent
  double x, y, z;
  uint8_t has_pos;
} WqlObj;

typedef struct {
  uint8_t instruction;
  uint8_t replication;
  uint8_t has_pos;
  double x, y, z;
  const uint8_t* parameter; int32_t parameter_len;  // NULL → absent
  const uint8_t* sender;    int32_t sender_len;     // NULL → absent
  const uint8_t* world;     int32_t world_len;      // NULL → absent
  const uint8_t* flex;      int32_t flex_len;       // NULL → absent
  int32_t n_records;
  int32_t n_entities;
  WqlObj records[WQL_MAX_OBJS];
  WqlObj entities[WQL_MAX_OBJS];
} WqlMsg;

enum {
  WQL_OK = 0,
  WQL_E_BOUNDS = -1,    // malformed/truncated buffer
  WQL_E_TOO_MANY = -2,  // > WQL_MAX_OBJS records or entities
  WQL_E_ALLOC = -3,
  WQL_E_CAPACITY = -4,  // entity columns too small — caller grows + retries
};

// ---------------------------------------------------------------- reader

namespace {

struct Reader {
  const uint8_t* buf;
  size_t len;

  bool in(size_t pos, size_t n) const {
    return pos <= len && n <= len - pos;
  }
  template <typename T>
  bool load(size_t pos, T* out) const {
    if (!in(pos, sizeof(T))) return false;
    std::memcpy(out, buf + pos, sizeof(T));
    return true;
  }
};

// Field position for a vtable slot; 0 if absent/malformed-absent.
static size_t field_pos(const Reader& r, size_t table, int slot, bool* err) {
  int32_t soff;
  if (!r.load<int32_t>(table, &soff)) { *err = true; return 0; }
  // vtable = table - soff (soffset may be negative)
  int64_t vt = static_cast<int64_t>(table) - soff;
  if (vt < 0 || !r.in(static_cast<size_t>(vt), 4)) { *err = true; return 0; }
  uint16_t vt_size;
  if (!r.load<uint16_t>(static_cast<size_t>(vt), &vt_size)) { *err = true; return 0; }
  size_t entry = static_cast<size_t>(vt) + 4 + 2 * static_cast<size_t>(slot);
  if (4 + 2 * (slot + 1) > vt_size) return 0;  // slot beyond vtable → default
  uint16_t foff;
  if (!r.load<uint16_t>(entry, &foff)) { *err = true; return 0; }
  if (foff == 0) return 0;
  size_t pos = table + foff;
  if (pos >= r.len) { *err = true; return 0; }
  return pos;
}

// Follow a uoffset32 at pos → target position.
static size_t indirect(const Reader& r, size_t pos, bool* err) {
  uint32_t uoff;
  if (!r.load<uint32_t>(pos, &uoff)) { *err = true; return 0; }
  size_t target = pos + uoff;
  if (target >= r.len) { *err = true; return 0; }
  return target;
}

// String/byte-vector at slot: view into the buffer.
static bool read_blob(const Reader& r, size_t table, int slot,
                      const uint8_t** out, int32_t* out_len, bool* err) {
  *out = nullptr; *out_len = 0;
  size_t fpos = field_pos(r, table, slot, err);
  if (*err || fpos == 0) return fpos != 0 && !*err;
  size_t s = indirect(r, fpos, err);
  if (*err) return false;
  uint32_t n;
  if (!r.load<uint32_t>(s, &n)) { *err = true; return false; }
  if (n > r.len || !r.in(s + 4, n)) { *err = true; return false; }
  *out = r.buf + s + 4;
  *out_len = static_cast<int32_t>(n);
  return true;
}

static uint8_t read_u8(const Reader& r, size_t table, int slot,
                       uint8_t dflt, bool* err) {
  size_t fpos = field_pos(r, table, slot, err);
  if (*err || fpos == 0) return dflt;
  uint8_t v;
  if (!r.load<uint8_t>(fpos, &v)) { *err = true; return dflt; }
  return v;
}

static bool read_vec3(const Reader& r, size_t table, int slot,
                      double* x, double* y, double* z, bool* err) {
  size_t fpos = field_pos(r, table, slot, err);
  if (*err || fpos == 0) return false;
  double v[3];
  if (!r.in(fpos, 24)) { *err = true; return false; }
  std::memcpy(v, r.buf + fpos, 24);
  *x = v[0]; *y = v[1]; *z = v[2];
  return true;
}

enum { OBJ_UUID = 0, OBJ_POSITION = 1, OBJ_WORLD = 2, OBJ_DATA = 3,
       OBJ_FLEX = 4 };
enum { MSG_INSTRUCTION = 0, MSG_PARAMETER = 1, MSG_SENDER = 2,
       MSG_WORLD = 3, MSG_REPLICATION = 4, MSG_RECORDS = 5,
       MSG_ENTITIES = 6, MSG_POSITION = 7, MSG_FLEX = 8 };

static bool read_obj(const Reader& r, size_t table, WqlObj* o, bool* err) {
  std::memset(o, 0, sizeof(WqlObj));
  read_blob(r, table, OBJ_UUID, &o->uuid, &o->uuid_len, err);
  if (*err) return false;
  read_blob(r, table, OBJ_WORLD, &o->world, &o->world_len, err);
  if (*err) return false;
  read_blob(r, table, OBJ_DATA, &o->data, &o->data_len, err);
  if (*err) return false;
  read_blob(r, table, OBJ_FLEX, &o->flex, &o->flex_len, err);
  if (*err) return false;
  o->has_pos = read_vec3(r, table, OBJ_POSITION, &o->x, &o->y, &o->z, err)
                   ? 1 : 0;
  return !*err;
}

static int read_obj_vector(const Reader& r, size_t table, int slot,
                           WqlObj* out, int32_t* out_n, bool* err) {
  *out_n = 0;
  size_t fpos = field_pos(r, table, slot, err);
  if (*err) return WQL_E_BOUNDS;
  if (fpos == 0) return WQL_OK;
  size_t vec = indirect(r, fpos, err);
  if (*err) return WQL_E_BOUNDS;
  uint32_t n;
  if (!r.load<uint32_t>(vec, &n)) return WQL_E_BOUNDS;
  if (n > WQL_MAX_OBJS) return WQL_E_TOO_MANY;
  if (!r.in(vec + 4, static_cast<size_t>(n) * 4)) return WQL_E_BOUNDS;
  for (uint32_t i = 0; i < n; i++) {
    size_t t = indirect(r, vec + 4 + 4 * i, err);
    if (*err) return WQL_E_BOUNDS;
    if (!read_obj(r, t, &out[i], err)) return WQL_E_BOUNDS;
  }
  *out_n = static_cast<int32_t>(n);
  return WQL_OK;
}

}  // namespace

extern "C" int wql_decode(const uint8_t* buf, size_t len, WqlMsg* out) {
  Reader r{buf, len};
  bool err = false;
  std::memset(out, 0, offsetof(WqlMsg, records));
  out->n_records = 0;
  out->n_entities = 0;

  uint32_t root_off;
  if (!r.load<uint32_t>(0, &root_off) || root_off >= len) return WQL_E_BOUNDS;
  size_t root = root_off;

  out->instruction = read_u8(r, root, MSG_INSTRUCTION, 0, &err);
  if (err) return WQL_E_BOUNDS;
  out->replication = read_u8(r, root, MSG_REPLICATION, 0, &err);
  if (err) return WQL_E_BOUNDS;
  read_blob(r, root, MSG_PARAMETER, &out->parameter, &out->parameter_len, &err);
  if (err) return WQL_E_BOUNDS;
  read_blob(r, root, MSG_SENDER, &out->sender, &out->sender_len, &err);
  if (err) return WQL_E_BOUNDS;
  read_blob(r, root, MSG_WORLD, &out->world, &out->world_len, &err);
  if (err) return WQL_E_BOUNDS;
  read_blob(r, root, MSG_FLEX, &out->flex, &out->flex_len, &err);
  if (err) return WQL_E_BOUNDS;
  out->has_pos = read_vec3(r, root, MSG_POSITION, &out->x, &out->y, &out->z,
                           &err) ? 1 : 0;
  if (err) return WQL_E_BOUNDS;

  int rc = read_obj_vector(r, root, MSG_RECORDS, out->records,
                           &out->n_records, &err);
  if (rc != WQL_OK || err) return rc != WQL_OK ? rc : WQL_E_BOUNDS;
  rc = read_obj_vector(r, root, MSG_ENTITIES, out->entities,
                       &out->n_entities, &err);
  if (rc != WQL_OK || err) return rc != WQL_OK ? rc : WQL_E_BOUNDS;
  return WQL_OK;
}

// ---------------------------------------------------------------- writer

namespace {

// Back-to-front FlatBuffers builder: offsets are measured from the END
// of the storage; final buffer is the tail slice.
struct Builder {
  std::vector<uint8_t> store;
  size_t head;       // index of first used byte
  size_t minalign = 1;

  explicit Builder(size_t cap = 1024) : store(cap), head(cap) {}

  size_t offset() const { return store.size() - head; }

  void grow(size_t need) {
    if (head >= need) return;
    size_t old_size = store.size();
    size_t new_size = old_size * 2;
    while (new_size - old_size + head < need) new_size *= 2;
    std::vector<uint8_t> bigger(new_size);
    std::memcpy(bigger.data() + (new_size - old_size), store.data(), old_size);
    head += new_size - old_size;
    store.swap(bigger);
  }

  void pad(size_t n) {
    grow(n);
    head -= n;
    std::memset(store.data() + head, 0, n);
  }

  // Align so that after writing `size` bytes, offset() % align == 0.
  void prep(size_t align, size_t extra) {
    if (align > minalign) minalign = align;
    size_t align_size = ((~(offset() + extra)) + 1) & (align - 1);
    pad(align_size);
  }

  void push(const void* src, size_t n) {
    grow(n);
    head -= n;
    std::memcpy(store.data() + head, src, n);
  }

  template <typename T>
  void push_scalar(T v) { push(&v, sizeof(T)); }

  // uoffset32 referencing an object at `target` (offset-from-end).
  void push_uoffset(size_t target) {
    prep(4, 0);
    uint32_t v = static_cast<uint32_t>(offset() + 4 - target);
    push_scalar<uint32_t>(v);
  }

  size_t create_blob(const uint8_t* data, size_t n, bool nul) {
    if (nul) { prep(4, n + 1); uint8_t z = 0; push(&z, 1); }
    else     { prep(4, n); }
    push(data, n);
    push_scalar<uint32_t>(static_cast<uint32_t>(n));
    return offset();
  }

  size_t create_vec3(double x, double y, double z) {
    prep(8, 24);
    double v[3] = {x, y, z};
    push(v, 24);
    return offset();
  }
};

struct TableBuilder {
  Builder& b;
  size_t start;                     // offset() at StartTable
  int max_slot = -1;
  size_t slot_off[16] = {0};        // field offset-from-end per slot

  explicit TableBuilder(Builder& b_) : b(b_), start(b_.offset()) {}

  void track(int slot) {
    slot_off[slot] = b.offset();
    if (slot > max_slot) max_slot = slot;
  }

  void field_u8(int slot, uint8_t v, uint8_t dflt) {
    if (v == dflt) return;
    b.prep(1, 0);
    b.push_scalar<uint8_t>(v);
    track(slot);
  }

  void field_uoffset(int slot, size_t target) {
    b.push_uoffset(target);
    track(slot);
  }

  void field_struct(int slot, size_t target) {
    // Structs are written immediately before; they must be inline at
    // the field position (flatbuffers invariant).
    (void)target;
    track(slot);
  }

  size_t end() {
    // soffset placeholder
    b.prep(4, 0);
    b.push_scalar<int32_t>(0);
    size_t table_start = b.offset();

    int n_slots = max_slot + 1;
    uint16_t vt_size = static_cast<uint16_t>(4 + 2 * n_slots);
    uint16_t tbl_size = static_cast<uint16_t>(table_start - start);

    // vtable entries, last slot first
    for (int i = n_slots - 1; i >= 0; i--) {
      uint16_t entry = slot_off[i]
          ? static_cast<uint16_t>(table_start - slot_off[i]) : 0;
      b.push_scalar<uint16_t>(entry);
    }
    b.push_scalar<uint16_t>(tbl_size);
    b.push_scalar<uint16_t>(vt_size);
    size_t vt = b.offset();

    // patch soffset: vtable relative to table
    int32_t soff = static_cast<int32_t>(vt - table_start);
    size_t table_pos = b.store.size() - table_start;
    std::memcpy(b.store.data() + table_pos, &soff, 4);
    return table_start;
  }
};

static size_t write_obj(Builder& b, const WqlObj* o) {
  size_t uuid_off = b.create_blob(o->uuid, o->uuid_len, true);
  size_t world_off = b.create_blob(o->world, o->world_len, true);
  size_t data_off = o->data ? b.create_blob(o->data, o->data_len, true) : 0;
  size_t flex_off = o->flex ? b.create_blob(o->flex, o->flex_len, false) : 0;

  TableBuilder t(b);
  t.field_uoffset(OBJ_UUID, uuid_off);
  if (o->has_pos) {
    b.create_vec3(o->x, o->y, o->z);
    t.field_struct(OBJ_POSITION, 0);
  }
  t.field_uoffset(OBJ_WORLD, world_off);
  if (data_off) t.field_uoffset(OBJ_DATA, data_off);
  if (flex_off) t.field_uoffset(OBJ_FLEX, flex_off);
  return t.end();
}

static size_t write_obj_vector(Builder& b, const WqlObj* objs, int32_t n) {
  std::vector<size_t> offs(n);
  for (int32_t i = 0; i < n; i++) offs[i] = write_obj(b, &objs[i]);
  b.prep(4, static_cast<size_t>(n) * 4);
  for (int32_t i = n - 1; i >= 0; i--) b.push_uoffset(offs[i]);
  b.push_scalar<uint32_t>(static_cast<uint32_t>(n));
  return b.offset();
}

}  // namespace

extern "C" int wql_encode(const WqlMsg* in, uint8_t** out, size_t* out_len) {
  if (in->n_records > WQL_MAX_OBJS || in->n_entities > WQL_MAX_OBJS)
    return WQL_E_TOO_MANY;
  Builder b(1024);

  size_t records_vec = in->n_records
      ? write_obj_vector(b, in->records, in->n_records) : 0;
  size_t entities_vec = in->n_entities
      ? write_obj_vector(b, in->entities, in->n_entities) : 0;

  size_t param_off = in->parameter
      ? b.create_blob(in->parameter, in->parameter_len, true) : 0;
  size_t sender_off = in->sender
      ? b.create_blob(in->sender, in->sender_len, true) : 0;
  size_t world_off = in->world
      ? b.create_blob(in->world, in->world_len, true) : 0;
  size_t flex_off = in->flex
      ? b.create_blob(in->flex, in->flex_len, false) : 0;

  TableBuilder t(b);
  t.field_u8(MSG_INSTRUCTION, in->instruction, 0);
  if (param_off) t.field_uoffset(MSG_PARAMETER, param_off);
  if (sender_off) t.field_uoffset(MSG_SENDER, sender_off);
  if (world_off) t.field_uoffset(MSG_WORLD, world_off);
  t.field_u8(MSG_REPLICATION, in->replication, 0);
  if (records_vec) t.field_uoffset(MSG_RECORDS, records_vec);
  if (entities_vec) t.field_uoffset(MSG_ENTITIES, entities_vec);
  if (in->has_pos) {
    b.create_vec3(in->x, in->y, in->z);
    t.field_struct(MSG_POSITION, 0);
  }
  if (flex_off) t.field_uoffset(MSG_FLEX, flex_off);
  size_t root = t.end();

  // root uoffset, padded to minalign
  b.prep(std::max<size_t>(b.minalign, 4), 4);
  b.push_uoffset(root);

  size_t n = b.offset();
  uint8_t* mem = static_cast<uint8_t*>(std::malloc(n));
  if (!mem) return WQL_E_ALLOC;
  std::memcpy(mem, b.store.data() + b.head, n);
  *out = mem;
  *out_len = n;
  return WQL_OK;
}

extern "C" void wql_buffer_free(uint8_t* p) { std::free(p); }

extern "C" int wql_max_objs(void) { return WQL_MAX_OBJS; }

// ------------------------------------------- columnar entity ingest
//
// The wire→SoA fast path (consumer: worldql_server_tpu/protocol/
// entity_wire.py → entities/ingest.py): batch-decode the `entities`
// lists of a whole recv batch straight into preallocated SoA columns —
// binary uuid keys, f32 positions/velocities — with zero per-entity
// Python objects. The entities vector is read directly off the wire
// (no WqlObj scratch), so this path has NO WQL_MAX_OBJS cap; its only
// bound is the caller's column capacity.
//
// A buffer is FAST (status 1) only when the whole message is a plain
// entity upsert batch the columnar path can represent: Local/Global-
// Message, no parameter (removals and exotic parameters keep their
// object-path semantics), canonical 36-char uuids, every entity world
// empty-or-equal to the message world, position present. Anything else
// is status 0 and the caller routes those bytes through the ordinary
// codec — identical semantics, slower.

namespace {

inline int hexval(uint8_t c) {
  if (c >= '0' && c <= '9') return c - '0';
  if (c >= 'a' && c <= 'f') return c - 'a' + 10;
  if (c >= 'A' && c <= 'F') return c - 'A' + 10;
  return -1;
}

// canonical 8-4-4-4-12 uuid string → 16 bytes; false for any other
// format (Python's uuid.UUID accepts more — those take the object path)
bool parse_uuid36(const uint8_t* s, int32_t len, uint8_t* out) {
  if (len != 36 || s[8] != '-' || s[13] != '-' || s[18] != '-' ||
      s[23] != '-')
    return false;
  static const int at[16] = {0,  2,  4,  6,  9,  11, 14, 16,
                             19, 21, 24, 26, 28, 30, 32, 34};
  for (int i = 0; i < 16; i++) {
    const int hi = hexval(s[at[i]]);
    const int lo = hexval(s[at[i] + 1]);
    if (hi < 0 || lo < 0) return false;
    out[i] = static_cast<uint8_t>((hi << 4) | lo);
  }
  return true;
}

constexpr uint8_t INSTR_GLOBAL_MESSAGE = 6;
constexpr uint8_t INSTR_LOCAL_MESSAGE = 7;

// Validate one Record/Entity table the way the object decoder would
// read it (uuid canonical + world present + every blob/struct in
// bounds) WITHOUT materializing anything. The fast path must never
// accept a buffer the object path would reject — corruption in a field
// the columnar consumer ignores (records, data) still routes slow.
bool validate_obj(const Reader& r, size_t table, bool* err) {
  const uint8_t* u; int32_t ulen;
  read_blob(r, table, OBJ_UUID, &u, &ulen, err);
  uint8_t scratch[16];
  if (*err || u == nullptr || !parse_uuid36(u, ulen, scratch)) return false;
  const uint8_t* w; int32_t wlen;
  read_blob(r, table, OBJ_WORLD, &w, &wlen, err);
  if (*err || w == nullptr) return false;
  const uint8_t* d; int32_t dlen;
  read_blob(r, table, OBJ_DATA, &d, &dlen, err);
  if (*err) return false;
  read_blob(r, table, OBJ_FLEX, &d, &dlen, err);
  if (*err) return false;
  double x, y, z;
  read_vec3(r, table, OBJ_POSITION, &x, &y, &z, err);
  return !*err;
}

}  // namespace

extern "C" int64_t wql_entities_abi(void) { return 1; }

// Decode a recv batch. Per buffer: status[i] = 1 (columnar entity
// batch; envelope + rows written) or 0 (route through the object
// path). Entity rows land at ent_start[i]..+ent_count[i] in the shared
// columns. Returns total rows written, or WQL_E_CAPACITY when ent_cap
// cannot hold them (caller doubles the columns and retries).
extern "C" int64_t wql_decode_entities(
    const uint8_t* const* bufs, const int64_t* lens, int64_t n_bufs,
    int8_t* status, uint8_t* instr_out, uint8_t* sender_key,
    int64_t* world_off, int32_t* world_len_out, int64_t* ent_start,
    int32_t* ent_count, int64_t ent_cap, uint8_t* uuid_keys,
    float* pos_out, float* vel_out, uint8_t* has_vel) {
  int64_t total = 0;
  for (int64_t bi = 0; bi < n_bufs; bi++) {
    status[bi] = 0;
    instr_out[bi] = 0;
    world_off[bi] = 0;
    world_len_out[bi] = 0;
    ent_start[bi] = total;
    ent_count[bi] = 0;

    Reader r{bufs[bi], static_cast<size_t>(lens[bi])};
    bool err = false;
    uint32_t root_off;
    if (!r.load<uint32_t>(0, &root_off) || root_off >= r.len) continue;
    const size_t root = root_off;

    const uint8_t instr = read_u8(r, root, MSG_INSTRUCTION, 0, &err);
    if (err) continue;
    instr_out[bi] = instr;
    if (instr != INSTR_LOCAL_MESSAGE && instr != INSTR_GLOBAL_MESSAGE)
      continue;
    const uint8_t* param;
    int32_t param_len;
    read_blob(r, root, MSG_PARAMETER, &param, &param_len, &err);
    if (err || param != nullptr) continue;  // removal/exotic → object path
    const uint8_t* sender;
    int32_t sender_len;
    read_blob(r, root, MSG_SENDER, &sender, &sender_len, &err);
    if (err || sender == nullptr ||
        !parse_uuid36(sender, sender_len, sender_key + 16 * bi))
      continue;
    const uint8_t* world;
    int32_t wlen;
    read_blob(r, root, MSG_WORLD, &world, &wlen, &err);
    if (err || world == nullptr) continue;
    world_off[bi] = static_cast<int64_t>(world - bufs[bi]);
    world_len_out[bi] = wlen;
    // fields the columnar consumer ignores still classify: the object
    // decoder reads them, so corruption there must route slow
    const uint8_t* mfx;
    int32_t mfxlen;
    read_blob(r, root, MSG_FLEX, &mfx, &mfxlen, &err);
    if (err) continue;
    double mx, my, mz;
    read_vec3(r, root, MSG_POSITION, &mx, &my, &mz, &err);
    if (err) continue;
    {
      size_t rpos = field_pos(r, root, MSG_RECORDS, &err);
      if (err) continue;
      if (rpos != 0) {
        size_t rvec = indirect(r, rpos, &err);
        if (err) continue;
        uint32_t rn;
        if (!r.load<uint32_t>(rvec, &rn)) continue;
        if (!r.in(rvec + 4, static_cast<size_t>(rn) * 4)) continue;
        bool rec_ok = true;
        for (uint32_t i = 0; rec_ok && i < rn; i++) {
          size_t rt = indirect(r, rvec + 4 + 4 * i, &err);
          if (err || !validate_obj(r, rt, &err)) rec_ok = false;
        }
        if (!rec_ok || err) continue;
      }
    }

    // entities vector, read straight off the wire — no object cap
    size_t fpos = field_pos(r, root, MSG_ENTITIES, &err);
    if (err || fpos == 0) continue;
    size_t vec = indirect(r, fpos, &err);
    if (err) continue;
    uint32_t n;
    if (!r.load<uint32_t>(vec, &n) || n == 0) continue;
    if (!r.in(vec + 4, static_cast<size_t>(n) * 4)) continue;
    if (total + static_cast<int64_t>(n) > ent_cap) return WQL_E_CAPACITY;

    bool ok = true;
    for (uint32_t i = 0; ok && i < n; i++) {
      size_t t = indirect(r, vec + 4 + 4 * i, &err);
      if (err) { ok = false; break; }
      const uint8_t* u;
      int32_t ulen;
      read_blob(r, t, OBJ_UUID, &u, &ulen, &err);
      if (err || u == nullptr ||
          !parse_uuid36(u, ulen, uuid_keys + 16 * (total + i))) {
        ok = false;
        break;
      }
      const uint8_t* ew;
      int32_t ewlen;
      read_blob(r, t, OBJ_WORLD, &ew, &ewlen, &err);
      if (err || ew == nullptr) { ok = false; break; }
      // entity world must be the message world (empty = inherit, like
      // `ent.world_name or message.world_name`); anything else keeps
      // the object path's per-entity world semantics
      if (ewlen != 0 &&
          (ewlen != wlen ||
           std::memcmp(ew, world, static_cast<size_t>(wlen)) != 0)) {
        ok = false;
        break;
      }
      double x, y, z;
      if (!read_vec3(r, t, OBJ_POSITION, &x, &y, &z, &err) || err) {
        ok = false;  // position required — the object path raises
        break;
      }
      const uint8_t* dd;
      int32_t ddlen;
      read_blob(r, t, OBJ_DATA, &dd, &ddlen, &err);
      if (err) { ok = false; break; }  // object decoder reads data too
      float* p = pos_out + 3 * (total + i);
      p[0] = static_cast<float>(x);
      p[1] = static_cast<float>(y);
      p[2] = static_cast<float>(z);
      const uint8_t* fx;
      int32_t fxlen;
      read_blob(r, t, OBJ_FLEX, &fx, &fxlen, &err);
      if (err) { ok = false; break; }
      float* v = vel_out + 3 * (total + i);
      if (fx != nullptr && fxlen >= 12) {
        std::memcpy(v, fx, 12);  // 12 LE f32 bytes (host is LE)
        has_vel[total + i] = 1;
      } else {  // absent/short flex = no velocity change
        v[0] = v[1] = v[2] = 0.0f;
        has_vel[total + i] = 0;
      }
    }
    if (!ok) { ent_count[bi] = 0; continue; }
    ent_count[bi] = static_cast<int32_t>(n);
    total += n;
    status[bi] = 1;
  }
  return total;
}

// --------------------------------------- per-cohort frame encoding

namespace {

void unparse_uuid(const uint8_t* b, uint8_t* out36) {
  static const char hexd[] = "0123456789abcdef";
  int j = 0;
  for (int i = 0; i < 16; i++) {
    out36[j++] = hexd[b[i] >> 4];
    out36[j++] = hexd[b[i] & 0xF];
    if (i == 3 || i == 5 || i == 7 || i == 9) out36[j++] = '-';
  }
}

}  // namespace

// Encode n "entity.frame" neighbor frames (LocalMessage, one entity
// each) sharing ONE world in a single native pass — the serialize-once
// cohort encode of entities/plane._build_frames. Frames are
// byte-identical to wql_encode of the equivalent Message (same builder,
// same write order), concatenated into one malloc'd buffer; frame i is
// (*out)[out_off[i] .. +out_len[i]]. Free with wql_buffer_free.
extern "C" int wql_encode_entity_frames(
    const uint8_t* sender_keys, const uint8_t* ent_keys, const double* pos,
    int64_t n, const uint8_t* world, int32_t world_len, uint8_t** out,
    int64_t* out_off, int64_t* out_len) {
  static const uint8_t PARAM[] = "entity.frame";
  std::vector<uint8_t> acc;
  acc.reserve(static_cast<size_t>(n) * 256);
  int64_t cursor = 0;
  for (int64_t i = 0; i < n; i++) {
    uint8_t sender36[36], ent36[36];
    unparse_uuid(sender_keys + 16 * i, sender36);
    unparse_uuid(ent_keys + 16 * i, ent36);
    const double* p = pos + 3 * i;

    Builder b(512);
    WqlObj ent;
    std::memset(&ent, 0, sizeof(ent));
    ent.uuid = ent36;
    ent.uuid_len = 36;
    ent.world = world;
    ent.world_len = world_len;
    ent.has_pos = 1;
    ent.x = p[0];
    ent.y = p[1];
    ent.z = p[2];
    // mirror wql_encode's write order exactly (byte parity)
    size_t entities_vec = write_obj_vector(b, &ent, 1);
    size_t param_off = b.create_blob(PARAM, sizeof(PARAM) - 1, true);
    size_t sender_off = b.create_blob(sender36, 36, true);
    size_t world_off = b.create_blob(world, world_len, true);
    TableBuilder t(b);
    t.field_u8(MSG_INSTRUCTION, INSTR_LOCAL_MESSAGE, 0);
    t.field_uoffset(MSG_PARAMETER, param_off);
    t.field_uoffset(MSG_SENDER, sender_off);
    t.field_uoffset(MSG_WORLD, world_off);
    t.field_uoffset(MSG_ENTITIES, entities_vec);
    b.create_vec3(p[0], p[1], p[2]);
    t.field_struct(MSG_POSITION, 0);
    size_t root = t.end();
    b.prep(std::max<size_t>(b.minalign, 4), 4);
    b.push_uoffset(root);

    const size_t len = b.offset();
    acc.insert(acc.end(), b.store.begin() + b.head,
               b.store.begin() + b.head + len);
    out_off[i] = cursor;
    out_len[i] = static_cast<int64_t>(len);
    cursor += static_cast<int64_t>(len);
  }
  uint8_t* mem = static_cast<uint8_t*>(std::malloc(cursor ? cursor : 1));
  if (!mem) return WQL_E_ALLOC;
  if (cursor) std::memcpy(mem, acc.data(), static_cast<size_t>(cursor));
  *out = mem;
  return WQL_OK;
}

// Interest-managed frames (ISSUE 18; batched from records, ISSUE 44).
//
// An interest frame is a LocalMessage whose parameter is the caller's
// stamped "entity.frame.{full,fullc,delta}" string, carrying n entities
// of one world — live entries as positioned entities, departures
// (tomb[i] != 0) as the same entity at its last-known position plus a
// 1-byte flex tombstone marker (short flex is ignored by the velocity
// decode, so pre-interest readers see a harmless entity). The sender is
// the NIL uuid: these frames originate from the server, not a peer.
//
// Such an entity has only fixed-length parts, and every offset inside a
// table is relative, so the bytes write_obj emits for it depend on
// nothing but (tombstone?, the frame's world, Builder::offset() & 7
// before the call): the alignment pads are functions of the offset
// mod 8 and all else is the input. EntityRecords keeps those bytes per
// (offset & 7, tombstone) as the GENERIC write_obj wrote them on a side
// Builder padded to that state; an entity is then one memcpy of its
// record, its uuid unparsed into place and 24 bytes of position. A
// change of write_obj changes the records with it.

namespace {

struct EntityRecords {
  // more than write_obj emits for one such entity, less its world: four
  // blobs' pads and lengths, a padded Vec3, four uoffsets, the soffset
  // and a five-slot vtable
  static constexpr size_t kBound = 160;
  struct Record {
    const uint8_t* bytes = nullptr;  // into `store`; null = not built
    uint32_t len = 0;                // bytes write_obj emitted
    uint32_t uuid_at = 0;            // the 36 uuid chars, from the front
    uint32_t pos_at = 0;             // the 24 position bytes
    uint32_t table_rel = 0;          // table offset-from-end less the state
  };
  const uint8_t* world = nullptr;
  int32_t world_len = -1;
  std::vector<uint8_t> store;
  size_t used = 0;
  Record recs[8][2];

  // Records are per world: another world drops them.
  void bind(const uint8_t* w, int32_t wl) {
    if (wl == world_len && (wl == 0 || std::memcmp(w, world, wl) == 0)) {
      world = w;  // equal bytes, but the caller's pointer of THIS frame
      return;
    }
    world = w;
    world_len = wl;
    used = 0;
    const size_t all = 16 * (kBound + static_cast<size_t>(wl));
    if (store.size() < all) store.resize(all);
    for (auto& row : recs)
      for (auto& rec : row) rec = Record();
  }

  // write_obj's bytes for one sentinel entity at `state`.
  void emit(Builder& sb, size_t state, bool tomb, uint8_t fill,
            size_t* table) const {
    static const uint8_t TOMB1[] = {0};
    uint8_t uuid36[36];
    std::memset(uuid36, fill, sizeof(uuid36));
    uuid36[8] = uuid36[13] = uuid36[18] = uuid36[23] = '-';
    sb.head = sb.store.size();
    sb.pad(state);
    WqlObj ent;
    std::memset(&ent, 0, sizeof(ent));
    ent.uuid = uuid36;
    ent.uuid_len = 36;
    ent.world = world;
    ent.world_len = world_len;
    ent.has_pos = 1;
    std::memset(&ent.x, fill, sizeof(double));
    ent.y = ent.x;
    ent.z = ent.x;
    if (tomb) {
      ent.flex = TOMB1;
      ent.flex_len = 1;
    }
    *table = write_obj(sb, &ent);
  }

  // The record of (state, tomb), built at first use: write_obj runs
  // twice with different sentinels, and the bytes that differ ARE the
  // uuid's and the position's places, whatever the world holds.
  const Record* get(size_t state, bool tomb) {
    Record& rec = recs[state][tomb ? 1 : 0];
    if (rec.bytes != nullptr) return &rec;
    Builder a(256 + static_cast<size_t>(world_len));
    Builder b(256 + static_cast<size_t>(world_len));
    size_t table_a, table_b;
    emit(a, state, tomb, '0', &table_a);
    emit(b, state, tomb, 'f', &table_b);
    const size_t len = a.offset() - state;
    if (b.offset() - state != len || table_a != table_b ||
        used + len > store.size())
      return nullptr;
    const uint8_t* pa = a.store.data() + a.head;
    const uint8_t* pb = b.store.data() + b.head;
    size_t at = 0;
    while (at < len && pa[at] == pb[at]) at++;
    const size_t pos_at = at;          // the table precedes the blobs
    while (at < len && pa[at] != pb[at]) at++;
    if (at - pos_at != 24) return nullptr;
    while (at < len && pa[at] == pb[at]) at++;
    const size_t uuid_at = at;         // 8-4-4-4-12, the dashes equal
    size_t differ = 0;
    for (; at < len; at++) differ += pa[at] != pb[at];
    if (uuid_at + 36 > len || differ != 32 ||
        pa[uuid_at + 35] == pb[uuid_at + 35])
      return nullptr;
    uint8_t* dst = store.data() + used;
    std::memcpy(dst, pa, len);
    used += len;
    rec.bytes = dst;
    rec.len = static_cast<uint32_t>(len);
    rec.uuid_at = static_cast<uint32_t>(uuid_at);
    rec.pos_at = static_cast<uint32_t>(pos_at);
    rec.table_rel = static_cast<uint32_t>(table_a - state);
    return &rec;
  }
};

// 16 key bytes as the 32 hex digits of a canonical uuid string whose
// dashes are already in place (the record's own).
inline void put_uuid_digits(const uint8_t* key, uint8_t* out36) {
  static const char hexd[] = "0123456789abcdef";
  static const int at[16] = {0,  2,  4,  6,  9,  11, 14, 16,
                             19, 21, 24, 26, 28, 30, 32, 34};
  for (int i = 0; i < 16; i++) {
    out36[at[i]] = hexd[key[i] >> 4];
    out36[at[i] + 1] = hexd[key[i] & 0xF];
  }
}

inline void store_u32(uint8_t* at, size_t v) {
  const uint32_t w = static_cast<uint32_t>(v);
  std::memcpy(at, &w, 4);
}

}  // namespace

// Encode n_frames interest frames in ONE pass (the one export for
// them). Frame f: parameter params[f], world worlds[f], and the
// entities [bounds[f], bounds[f + 1]) of three shared columns: [N,16]
// u8 uuid keys, [N,3] f64 positions, [N] u8 tombstone flags. Every
// frame is byte-identical to wql_encode / serialize_message of the
// equivalent Message (same write order; the entities field omitted when
// a frame has none, as the object encoders omit empty vectors), and
// does not depend on its neighbours in the batch. Entities are written
// from records (EntityRecords); the vector, the three blobs and the
// root table by the generic Builder on a side buffer padded to the
// frame's alignment state. Frames are laid back to front into one
// malloc'd buffer, each where it stays: frame f is
// (*out)[out_off[f] .. +out_len[f]], its parameter's first byte at
// out_param[f] within it (the caller patches its stamp there). Returns
// the entities written from records (every entity of the batch), or a
// negative error. Free with wql_buffer_free.
extern "C" int64_t wql_encode_interest_frames(
    int64_t n_frames, const uint8_t* const* params, const int32_t* param_lens,
    const uint8_t* const* worlds, const int32_t* world_lens,
    const int64_t* bounds, const uint8_t* ent_keys, const double* pos,
    const uint8_t* tomb, uint8_t** out, int64_t* out_off, int64_t* out_len,
    int64_t* out_param) {
  static const uint8_t NIL36[] = "00000000-0000-0000-0000-000000000000";
  if (n_frames < 0 || out == nullptr) return WQL_E_BOUNDS;

  // an upper bound a frame: nothing is written past what it uses
  size_t cap = 8;
  for (int64_t f = 0; f < n_frames; f++) {
    const int64_t n = bounds[f + 1] - bounds[f];
    if (n < 0 || n > INT32_MAX || param_lens[f] < 0 || world_lens[f] < 0 ||
        params[f] == nullptr || worlds[f] == nullptr)
      return WQL_E_BOUNDS;
    cap += 256 + static_cast<size_t>(param_lens[f]) +
           static_cast<size_t>(world_lens[f]) +
           static_cast<size_t>(n) *
               (EntityRecords::kBound + static_cast<size_t>(world_lens[f]));
  }
  uint8_t* mem = static_cast<uint8_t*>(std::malloc(cap));
  if (!mem) return WQL_E_ALLOC;

  EntityRecords records;
  Builder head(512);
  std::vector<size_t> offs;
  int64_t recorded = 0;
  uint8_t* end = mem + cap;         // one past the frame being written
  for (int64_t f = n_frames - 1; f >= 0; f--) {
    const int64_t lo = bounds[f];
    const int64_t n = bounds[f + 1] - lo;
    const uint8_t* world = worlds[f];
    const int32_t world_len = world_lens[f];
    size_t off = 0;                 // bytes of this frame so far, from `end`
    if (n > 0) {
      records.bind(world, world_len);
      offs.resize(static_cast<size_t>(n));
      for (int64_t i = 0; i < n; i++) {
        const EntityRecords::Record* rec =
            records.get(off & 7, tomb != nullptr && tomb[lo + i] != 0);
        if (rec == nullptr) { std::free(mem); return WQL_E_BOUNDS; }
        uint8_t* at = end - off - rec->len;
        std::memcpy(at, rec->bytes, rec->len);
        put_uuid_digits(ent_keys + 16 * (lo + i), at + rec->uuid_at);
        std::memcpy(at + rec->pos_at, pos + 3 * (lo + i), 24);
        offs[static_cast<size_t>(i)] = off + rec->table_rel;
        off += rec->len;
      }
      recorded += n;
      // the vector of table offsets: prep(4, 4n), last entity first
      const size_t padding = (~off + 1) & 3;
      std::memset(end - off - padding, 0, padding);
      off += padding;
      for (int64_t i = n - 1; i >= 0; i--) {
        off += 4;
        store_u32(end - off, off - offs[static_cast<size_t>(i)]);
      }
      off += 4;
      store_u32(end - off, static_cast<size_t>(n));
    }
    // parameter, sender, world, root: the generic writer, on a side
    // Builder that stands where this frame stands mod 8 (n == 0: at 0,
    // and no Vec3 has raised minalign)
    const size_t state = off & 7;
    head.head = head.store.size();
    head.minalign = n > 0 ? 8 : 1;
    head.pad(state);
    size_t param_off = head.create_blob(params[f], param_lens[f], true);
    size_t sender_off = head.create_blob(NIL36, 36, true);
    size_t world_off = head.create_blob(world, world_len, true);
    TableBuilder t(head);
    t.field_u8(MSG_INSTRUCTION, INSTR_LOCAL_MESSAGE, 0);
    t.field_uoffset(MSG_PARAMETER, param_off);
    t.field_uoffset(MSG_SENDER, sender_off);
    t.field_uoffset(MSG_WORLD, world_off);
    if (n > 0) t.field_uoffset(MSG_ENTITIES, state);
    size_t root = t.end();
    head.prep(std::max<size_t>(head.minalign, 4), 4);
    head.push_uoffset(root);
    const size_t head_len = head.offset() - state;
    std::memcpy(end - off - head_len, head.store.data() + head.head,
                head_len);
    const size_t len = off + head_len;
    out_off[f] = (end - len) - mem;
    out_len[f] = static_cast<int64_t>(len);
    // the blob's chars follow its u32 length
    out_param[f] = static_cast<int64_t>(len - (off + param_off - state) + 4);
    end -= len;
  }
  *out = mem;
  return recorded;
}
