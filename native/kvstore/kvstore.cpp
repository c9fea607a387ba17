// Native persistent KV store: the framework's analog of the reference's
// RocksDB dependency (reference package.yaml:32-33, used by the header
// chain at src/Haskoin/Node/Chain.hs:73-84,233-263,454-491).
//
// Design: append-only log + in-memory ordered index (std::map), replayed
// on open with torn-tail truncation, compacted when dead bytes dominate.
//
// Two on-disk modes, decided at open time (ISSUE 11):
//
//  * LEGACY v1: a single file of op(u8) klen(u32le) vlen(u32le) key value
//    records — kept for paths with no v2 artifacts, bit-compatible with
//    what this engine always wrote (the Python v2 reader replays it).
//  * v2 SEGMENTED (the format the Python LogKV writes, ISSUE 9): a base
//    snapshot/legacy file plus `<base>.NNNNNNNN.seg` segment files, each
//    opening with a TPK2 header (magic, version u16, kind u16, seq u64)
//    and carrying crc32(u32) seq(u32) op(u8) klen(u32) vlen(u32) records
//    where the CRC covers everything after itself.  This engine now
//    REPLAYS that format (CRC + per-segment sequence validated, torn
//    tails of the last file truncated) and APPENDS to it by opening a
//    fresh segment of its own — so `open_store(path, engine="native")`
//    serves the directory the node actually writes, and the Python
//    reader replays the result bit-identically (pinned by
//    tests/test_native_v2.py).
//
// Recovery division of labor: a torn tail of the LAST file is truncated
// here exactly like the Python reader's quiet path, but mid-log damage
// (a sealed file failing CRC/sequence checks, or unparseable bytes with
// valid successor records) REFUSES to open — quarantining salvage is
// LogKV's richer recovery path, and silently serving a prefix of acked
// data is the one thing a fallback engine must never do.
//
// Exposed as a C ABI for ctypes (tpunode/native.py).  Single-writer,
// like the reference's usage of RocksDB (one Chain actor owns the DB).

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <algorithm>
#include <map>
#include <string>
#include <vector>

#ifdef _WIN32
#error "POSIX only"
#endif
#include <dirent.h>
#include <fcntl.h>
#include <unistd.h>

namespace {

constexpr uint8_t OP_PUT = 1;
constexpr uint8_t OP_DEL = 2;
constexpr size_t REC_HDR = 9;        // v1: 1 + 4 + 4
constexpr size_t REC_V2_HDR = 17;    // crc(4) seq(4) op(1) klen(4) vlen(4)
constexpr size_t FILE_HDR = 16;      // magic(4) version(2) kind(2) seq(8)
constexpr uint16_t FMT_VERSION = 2;
constexpr uint16_t KIND_LOG = 0;
constexpr uint16_t KIND_SNAPSHOT = 1;
const char MAGIC[4] = {'T', 'P', 'K', '2'};
constexpr uint64_t SEG_LIMIT = 64ull << 20;  // rotation size, LogKV default

// zlib-compatible CRC-32 (polynomial 0xEDB88320), slicing-by-8: a block's
// UTXO delta is 12 MB of records a block (kv_frame_v2), where a byte a
// step cost as much as the write itself.
struct Crc32Table {
  uint32_t t[8][256];
  Crc32Table() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; ++i)
      for (int s = 1; s < 8; ++s)
        t[s][i] = t[0][t[s - 1][i] & 0xFF] ^ (t[s - 1][i] >> 8);
  }
};

uint32_t crc32(const uint8_t *p, size_t n) {
  static const Crc32Table tab;
  uint32_t c = 0xFFFFFFFFu;
  for (; n >= 8; p += 8, n -= 8) {
    uint32_t lo, hi;  // little-endian targets only, as put_u32 below
    memcpy(&lo, p, 4);
    memcpy(&hi, p + 4, 4);
    lo ^= c;
    c = tab.t[7][lo & 0xFF] ^ tab.t[6][(lo >> 8) & 0xFF] ^
        tab.t[5][(lo >> 16) & 0xFF] ^ tab.t[4][lo >> 24] ^
        tab.t[3][hi & 0xFF] ^ tab.t[2][(hi >> 8) & 0xFF] ^
        tab.t[1][(hi >> 16) & 0xFF] ^ tab.t[0][hi >> 24];
  }
  for (; n; ++p, --n) c = tab.t[0][(c ^ *p) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

void put_u32(uint8_t *p, uint32_t v) { memcpy(p, &v, 4); }  // LE targets only
void put_u64(uint8_t *p, uint64_t v) { memcpy(p, &v, 8); }

bool fsync_dir(const std::string &dir) {
  int fd = open(dir.empty() ? "." : dir.c_str(), O_RDONLY);
  if (fd < 0) return false;
  bool ok = fsync(fd) == 0;
  close(fd);
  return ok;
}

std::string dirname_of(const std::string &path) {
  size_t cut = path.find_last_of('/');
  return cut == std::string::npos ? std::string(".") : path.substr(0, cut);
}

std::string basename_of(const std::string &path) {
  size_t cut = path.find_last_of('/');
  return cut == std::string::npos ? path : path.substr(cut + 1);
}

std::string seg_path(const std::string &base, uint64_t seq) {
  char buf[32];
  snprintf(buf, sizeof(buf), ".%08llu.seg", (unsigned long long)seq);
  return base + buf;
}

// (seq, path) for every segment of `base`, ascending.
std::vector<std::pair<uint64_t, std::string>> list_segments(
    const std::string &base) {
  std::vector<std::pair<uint64_t, std::string>> out;
  std::string dir = dirname_of(base);
  std::string prefix = basename_of(base) + ".";
  DIR *d = opendir(dir.c_str());
  if (!d) return out;
  while (dirent *e = readdir(d)) {
    std::string name = e->d_name;
    if (name.size() <= prefix.size() + 4) continue;
    if (name.compare(0, prefix.size(), prefix) != 0) continue;
    if (name.compare(name.size() - 4, 4, ".seg") != 0) continue;
    std::string mid = name.substr(prefix.size(), name.size() - prefix.size() - 4);
    if (mid.empty() ||
        mid.find_first_not_of("0123456789") != std::string::npos)
      continue;
    out.emplace_back(strtoull(mid.c_str(), nullptr, 10), dir + "/" + name);
  }
  closedir(d);
  std::sort(out.begin(), out.end());
  return out;
}

bool file_has_magic(const std::string &path) {
  FILE *f = fopen(path.c_str(), "rb");
  if (!f) return false;
  char head[4];
  bool ok = fread(head, 1, 4, f) == 4 && memcmp(head, MAGIC, 4) == 0;
  fclose(f);
  return ok;
}

// Does `buf[from..]` hold a CRC-valid v2 record with a plausible forward
// sequence number at ANY offset?  A real torn write leaves nothing after
// the cut, so a hit means mid-log corruption, not a tear (mirror of the
// Python reader's _resync_finds_record).
bool resync_finds_record(const std::vector<char> &raw, size_t from,
                         uint32_t expect_seq) {
  const uint8_t *buf = reinterpret_cast<const uint8_t *>(raw.data());
  size_t n = raw.size();
  uint64_t horizon = uint64_t(expect_seq) + 1000000;
  for (size_t off = from; off + REC_V2_HDR <= n; ++off) {
    uint8_t op = buf[off + 8];
    if (op != OP_PUT && op != OP_DEL) continue;
    uint32_t crc, seq, klen, vlen;
    memcpy(&crc, buf + off, 4);
    memcpy(&seq, buf + off + 4, 4);
    memcpy(&klen, buf + off + 9, 4);
    memcpy(&vlen, buf + off + 13, 4);
    if (seq < expect_seq || uint64_t(seq) > horizon) continue;
    size_t end = off + REC_V2_HDR + size_t(klen) + vlen;
    if (end > n) continue;
    if (crc32(buf + off + 4, end - off - 4) == crc) return true;
  }
  return false;
}

struct Store {
  std::string path;
  std::map<std::string, std::string> data;
  FILE* file = nullptr;
  uint64_t dead_bytes = 0;
  uint64_t live_bytes = 0;
  bool v2 = false;              // segmented-log mode
  uint64_t active_seq = 0;      // v2: active segment sequence number
  uint32_t rec_seq = 0;         // v2: next record seq in the active segment
  uint64_t active_bytes = 0;    // v2: bytes in the active segment
  std::vector<std::pair<uint64_t, std::string>> segments;  // v2: sealed

  ~Store() {
    if (file) fclose(file);
  }

  size_t rec_overhead() const { return v2 ? REC_V2_HDR : REC_HDR; }

  void note_replace(const std::string& key) {
    auto it = data.find(key);
    if (it != data.end()) {
      uint64_t dead = rec_overhead() + key.size() + it->second.size();
      dead_bytes += dead;
      live_bytes -= dead;
    }
  }

  void apply(uint8_t op, std::string key, const char *val, size_t vlen,
             size_t rec_size) {
    note_replace(key);
    if (op == OP_PUT) {
      data[std::move(key)] = std::string(val, vlen);
      live_bytes += rec_size;
    } else {
      data.erase(key);
      dead_bytes += rec_size;
    }
  }

  static void put_rec_v1(std::string& out, uint8_t op, const char* k,
                         uint32_t klen, const char* v, uint32_t vlen) {
    uint8_t hdr[REC_HDR];
    hdr[0] = op;
    put_u32(hdr + 1, klen);
    put_u32(hdr + 5, vlen);
    out.append(reinterpret_cast<char *>(hdr), REC_HDR);
    out.append(k, klen);
    if (vlen) out.append(v, vlen);
  }

  void put_rec_v2(std::string& out, uint8_t op, const char* k, uint32_t klen,
                  const char* v, uint32_t vlen) {
    uint8_t hdr[REC_V2_HDR];
    put_u32(hdr + 4, rec_seq++);
    hdr[8] = op;
    put_u32(hdr + 9, klen);
    put_u32(hdr + 13, vlen);
    size_t body_at = out.size() + 4;
    out.append(reinterpret_cast<char *>(hdr), REC_V2_HDR);
    out.append(k, klen);
    if (vlen) out.append(v, vlen);
    uint32_t crc = crc32(
        reinterpret_cast<const uint8_t *>(out.data()) + body_at,
        out.size() - body_at);
    memcpy(&out[body_at - 4], &crc, 4);
  }

  // -- replay ---------------------------------------------------------------

  enum ReplayResult { RP_OK, RP_FAIL };

  static bool read_all(const std::string &p, std::vector<char> &raw) {
    FILE *f = fopen(p.c_str(), "rb");
    if (!f) return false;
    fseek(f, 0, SEEK_END);
    long sz = ftell(f);
    fseek(f, 0, SEEK_SET);
    raw.resize(size_t(sz));
    bool ok = !sz || fread(raw.data(), 1, raw.size(), f) == raw.size();
    fclose(f);
    return ok;
  }

  // v1 records from `raw[pos..]`; anomalies stop the scan.  In the last
  // file the unparseable tail is truncated away (pre-v2 behavior); in a
  // sealed file it is a hard failure.
  ReplayResult replay_v1(const std::string &p, std::vector<char> &raw,
                         size_t pos, bool is_last) {
    size_t good = pos;
    while (pos + REC_HDR <= raw.size()) {
      uint8_t op = uint8_t(raw[pos]);
      uint32_t klen, vlen;
      memcpy(&klen, raw.data() + pos + 1, 4);
      memcpy(&vlen, raw.data() + pos + 5, 4);
      size_t end = pos + REC_HDR + size_t(klen) + vlen;
      if (end > raw.size() || (op != OP_PUT && op != OP_DEL)) break;
      apply(op, std::string(raw.data() + pos + REC_HDR, klen),
            raw.data() + pos + REC_HDR + klen, vlen, end - pos);
      pos = end;
      good = pos;
    }
    if (good < raw.size()) {
      if (!is_last) return RP_FAIL;
      if (truncate(p.c_str(), off_t(good)) != 0) return RP_FAIL;
    }
    return RP_OK;
  }

  // v2 records after the file header; CRC + sequence validated.  Torn
  // tail of the last file truncated; anything else refuses (salvage is
  // the Python reader's job).
  ReplayResult replay_v2(const std::string &p, std::vector<char> &raw,
                         bool is_last) {
    if (raw.size() < FILE_HDR) {
      // header itself torn: an empty just-created file
      if (!is_last) return RP_FAIL;
      return truncate(p.c_str(), 0) == 0 ? RP_OK : RP_FAIL;
    }
    uint16_t version;
    memcpy(&version, raw.data() + 4, 2);
    if (version > FMT_VERSION) return RP_FAIL;  // newer than this reader
    size_t pos = FILE_HDR, good = pos;
    uint32_t expect_seq = 0;
    const uint8_t *buf = reinterpret_cast<const uint8_t *>(raw.data());
    while (pos + REC_V2_HDR <= raw.size()) {
      uint32_t crc, seq, klen, vlen;
      memcpy(&crc, buf + pos, 4);
      memcpy(&seq, buf + pos + 4, 4);
      uint8_t op = buf[pos + 8];
      memcpy(&klen, buf + pos + 9, 4);
      memcpy(&vlen, buf + pos + 13, 4);
      size_t end = pos + REC_V2_HDR + size_t(klen) + vlen;
      if (end > raw.size()) break;  // cut mid-record
      if (seq != expect_seq || (op != OP_PUT && op != OP_DEL) ||
          crc32(buf + pos + 4, end - pos - 4) != crc) {
        // a COMPLETE record failing validation is corruption, torn or
        // not — refuse (the Python reader quarantines)
        return RP_FAIL;
      }
      apply(op, std::string(raw.data() + pos + REC_V2_HDR, klen),
            raw.data() + pos + REC_V2_HDR + klen, vlen, end - pos);
      pos = end;
      good = pos;
      ++expect_seq;
    }
    if (good < raw.size()) {
      if (!is_last) return RP_FAIL;
      // last file: a true tear has no valid successor records after the
      // cut — if one exists this is mid-log damage and must stay loud
      if (resync_finds_record(raw, good, expect_seq)) return RP_FAIL;
      if (truncate(p.c_str(), off_t(good)) != 0) return RP_FAIL;
    }
    if (is_last) rec_seq = expect_seq;
    return RP_OK;
  }

  ReplayResult replay_file(const std::string &p, bool is_last) {
    std::vector<char> raw;
    if (!read_all(p, raw)) return RP_FAIL;
    if (raw.size() >= 4 && memcmp(raw.data(), MAGIC, 4) == 0)
      return replay_v2(p, raw, is_last);
    return replay_v1(p, raw, 0, is_last);
  }

  // -- open -----------------------------------------------------------------

  bool open_v1() {
    std::vector<char> raw;
    FILE *probe = fopen(path.c_str(), "rb");
    if (probe) {
      fclose(probe);
      if (replay_file(path, /*is_last=*/true) != RP_OK) return false;
    }
    file = fopen(path.c_str(), "ab");
    return file != nullptr;
  }

  bool open_v2() {
    // stale compaction temp: contents are a subset of base+segments
    std::string tmp = path + ".compact";
    if (remove(tmp.c_str()) == 0) fsync_dir(dirname_of(path));
    segments = list_segments(path);
    FILE *probe = fopen(path.c_str(), "rb");
    if (probe) {
      fclose(probe);
      if (replay_file(path, /*is_last=*/segments.empty()) != RP_OK)
        return false;
    }
    for (size_t i = 0; i < segments.size(); ++i) {
      if (replay_file(segments[i].second,
                      /*is_last=*/i + 1 == segments.size()) != RP_OK)
        return false;
    }
    // Fresh segment for OUR appends (never resume another writer's
    // segment: the LogKV resume rules — headerless-husk handling,
    // mid-segment seq continuation — stay that engine's; an extra
    // segment replays identically everywhere).
    uint64_t next = segments.empty() ? 1 : segments.back().first + 1;
    return new_segment(next);
  }

  bool new_segment(uint64_t seq) {
    if (file) {
      fflush(file);
      fclose(file);
      file = nullptr;
      segments.emplace_back(active_seq, seg_path(path, active_seq));
    }
    std::string p = seg_path(path, seq);
    file = fopen(p.c_str(), "ab");
    if (!file) return false;
    uint8_t hdr[FILE_HDR];
    memcpy(hdr, MAGIC, 4);
    uint16_t v = FMT_VERSION, kind = KIND_LOG;
    memcpy(hdr + 4, &v, 2);
    memcpy(hdr + 6, &kind, 2);
    put_u64(hdr + 8, seq);
    if (fwrite(hdr, 1, FILE_HDR, file) != FILE_HDR) return false;
    if (fflush(file) != 0) return false;
    fsync(fileno(file));
    fsync_dir(dirname_of(path));
    active_seq = seq;
    active_bytes = FILE_HDR;
    rec_seq = 0;
    return true;
  }

  bool open() {
    v2 = !list_segments(path).empty() || file_has_magic(path);
    return v2 ? open_v2() : open_v1();
  }

  // -- write path -----------------------------------------------------------

  // `ops` parsed from the ABI blob: (op, key, value).
  bool commit(const std::vector<std::tuple<uint8_t, std::string, std::string>>
                  &ops,
              bool do_fsync) {
    if (v2 && active_bytes >= SEG_LIMIT) {
      if (!new_segment(active_seq + 1)) return false;
    }
    std::string blob;
    for (const auto &[op, k, val] : ops) {
      if (v2)
        put_rec_v2(blob, op, k.data(), uint32_t(k.size()), val.data(),
                   uint32_t(val.size()));
      else
        put_rec_v1(blob, op, k.data(), uint32_t(k.size()), val.data(),
                   uint32_t(val.size()));
    }
    if (fwrite(blob.data(), 1, blob.size(), file) != blob.size()) return false;
    if (fflush(file) != 0) return false;
    if (do_fsync && fsync(fileno(file)) != 0) return false;
    active_bytes += blob.size();
    for (const auto &[op, k, val] : ops)
      apply(op, k, val.data(), val.size(),
            rec_overhead() + k.size() + val.size());
    if (dead_bytes >= (1u << 20) && dead_bytes >= 3 * live_bytes)
      compact();  // opportunistic: the write above is already durable, and
                  // a failed compaction reopens the log and keeps going
    return file != nullptr;
  }

  // -- compaction -----------------------------------------------------------

  bool compact() { return v2 ? compact_v2() : compact_v1(); }

  bool compact_v1() {
    // The old log handle is only closed after the new file is fully
    // written; on ANY failure the handle is re-opened so the store stays
    // writable (a failed compaction must degrade, not poison the Store).
    std::string tmp = path + ".compact";
    FILE* f = fopen(tmp.c_str(), "wb");
    if (!f) return false;
    std::string blob;
    for (auto& [k, v] : data) {
      blob.clear();
      put_rec_v1(blob, OP_PUT, k.data(), uint32_t(k.size()),
                 v.data(), uint32_t(v.size()));
      if (fwrite(blob.data(), 1, blob.size(), f) != blob.size()) {
        fclose(f);
        remove(tmp.c_str());
        return false;
      }
    }
    if (fflush(f) != 0 || fsync(fileno(f)) != 0) {
      fclose(f);
      remove(tmp.c_str());
      return false;
    }
    fclose(f);
    fclose(file);
    file = nullptr;
    bool ok = rename(tmp.c_str(), path.c_str()) == 0;
    file = fopen(path.c_str(), "ab");  // reopen whichever file now exists
    if (!ok || !file) return false;
    dead_bytes = 0;
    live_bytes = 0;
    for (auto& [k, v] : data) live_bytes += REC_HDR + k.size() + v.size();
    return true;
  }

  // v2: write a full snapshot over the base path, then drop every sealed
  // segment and the pre-compaction active one.  Crash-safe in the LogKV
  // sense: before the rename the old base+segments are intact (the temp
  // is swept on open); after it the snapshot holds every record and any
  // leftover segment merely re-applies idempotent writes.
  bool compact_v2() {
    std::string tmp = path + ".compact";
    FILE *f = fopen(tmp.c_str(), "wb");
    if (!f) return false;
    uint8_t hdr[FILE_HDR];
    memcpy(hdr, MAGIC, 4);
    uint16_t ver = FMT_VERSION, kind = KIND_SNAPSHOT;
    memcpy(hdr + 4, &ver, 2);
    memcpy(hdr + 6, &kind, 2);
    put_u64(hdr + 8, 0);
    bool ok = fwrite(hdr, 1, FILE_HDR, f) == FILE_HDR;
    std::string blob;
    uint32_t snap_seq = 0;
    for (auto &[k, v] : data) {
      if (!ok) break;
      blob.clear();
      uint8_t rh[REC_V2_HDR];
      put_u32(rh + 4, snap_seq++);
      rh[8] = OP_PUT;
      put_u32(rh + 9, uint32_t(k.size()));
      put_u32(rh + 13, uint32_t(v.size()));
      blob.append(reinterpret_cast<char *>(rh), REC_V2_HDR);
      blob.append(k);
      blob.append(v);
      uint32_t crc = crc32(
          reinterpret_cast<const uint8_t *>(blob.data()) + 4,
          blob.size() - 4);
      memcpy(&blob[0], &crc, 4);
      ok = fwrite(blob.data(), 1, blob.size(), f) == blob.size();
    }
    if (!ok || fflush(f) != 0 || fsync(fileno(f)) != 0) {
      fclose(f);
      remove(tmp.c_str());
      return false;
    }
    fclose(f);
    fsync_dir(dirname_of(path));
    // seal the active segment so the whole pre-snapshot tail is doomed
    std::vector<std::pair<uint64_t, std::string>> doomed = segments;
    doomed.emplace_back(active_seq, seg_path(path, active_seq));
    fclose(file);
    file = nullptr;
    if (rename(tmp.c_str(), path.c_str()) != 0) {
      // degrade, stay writable: the old base+segments remain the store —
      // keep EVERY sealed segment tracked (including the just-sealed
      // active one) so a later successful compaction deletes them all;
      // forgetting them here would leave stale files that replay after
      // that newer snapshot and resurrect deleted keys
      remove(tmp.c_str());
      segments = doomed;
      return new_segment(doomed.back().first + 1);
    }
    segments.clear();
    fsync_dir(dirname_of(path));
    for (auto &[seq, p] : doomed) {
      (void)seq;
      remove(p.c_str());
    }
    fsync_dir(dirname_of(path));
    if (!new_segment(doomed.back().first + 1)) return false;
    dead_bytes = 0;
    live_bytes = 0;
    for (auto &[k, v] : data) live_bytes += REC_V2_HDR + k.size() + v.size();
    return true;
  }
};

}  // namespace

extern "C" {

void* kv_open(const char* path) {
  auto* s = new Store();
  s->path = path;
  if (!s->open()) {
    delete s;
    return nullptr;
  }
  return s;
}

void kv_close(void* h) { delete static_cast<Store*>(h); }

// 1 = v2 segmented directory, 0 = legacy v1 single file.
int kv_format(void* h) { return static_cast<Store*>(h)->v2 ? 1 : 0; }

// 1 = found (out/outlen set, free with kv_buf_free), 0 = missing.
int kv_get(void* h, const char* key, uint32_t klen, char** out,
           uint64_t* outlen) {
  auto* s = static_cast<Store*>(h);
  auto it = s->data.find(std::string(key, klen));
  if (it == s->data.end()) return 0;
  *outlen = it->second.size();
  *out = static_cast<char*>(malloc(it->second.size() ? it->second.size() : 1));
  memcpy(*out, it->second.data(), it->second.size());
  return 1;
}

// blob = concatenated records in the v1 ABI format (op u8, klen u32le,
// vlen u32le, key, value) regardless of the on-disk mode.  0 = ok.
int kv_write_batch(void* h, const char* blob, uint64_t len, int do_fsync) {
  auto* s = static_cast<Store*>(h);
  size_t pos = 0;
  std::vector<std::tuple<uint8_t, std::string, std::string>> ops;
  while (pos + REC_HDR <= len) {
    uint8_t op = static_cast<uint8_t>(blob[pos]);
    uint32_t klen, vlen;
    memcpy(&klen, blob + pos + 1, 4);
    memcpy(&vlen, blob + pos + 5, 4);
    size_t end = pos + REC_HDR + static_cast<size_t>(klen) + vlen;
    if (end > len || (op != OP_PUT && op != OP_DEL)) return -1;
    ops.emplace_back(op, std::string(blob + pos + REC_HDR, klen),
                     std::string(blob + pos + REC_HDR + klen, vlen));
    pos = end;
  }
  if (pos != len) return -1;
  return s->commit(ops, do_fsync != 0) ? 0 : -2;
}

// Frame a v1 batch blob as the v2 records LogKV appends, for a caller that
// holds no store handle (tpunode/store.py LogKV.write_delta; ctypes drops
// the GIL for the call).  `blob` is a DELTA: v1 records (op u8, klen u32le,
// vlen u32le, key, value), every put before every delete, deletes without
// a value.  `out` receives, per record,
//   crc32 | seq, op, ns_len + klen, vlen | ns ++ key | value
// with seq counting up from seq0 and the CRC over everything after itself.
// Beside it the same keys and values in columns, for the index: `keys` =
// every ns ++ key end to end with `klens` their lengths, `vals` = the puts'
// values with `vlens`.  counts = {records, puts, bytes of out, of keys, of
// vals}.  With out == NULL the call only validates and counts.  Returns 0;
// -1 = malformed blob (bad opcode, a length past the end, a put after a
// delete, a delete with a value); -2 = the blob is not what was counted.
int kv_frame_v2(const uint8_t *blob, uint64_t len, const uint8_t *ns,
                uint32_t ns_len, uint32_t seq0, uint8_t *out, uint8_t *keys,
                int32_t *klens, uint8_t *vals, int32_t *vlens,
                uint64_t *counts) {
  uint64_t pos = 0, w = 0, kw = 0, vw = 0, n = 0, puts = 0;
  bool deleting = false;
  while (pos < len) {
    if (len - pos < REC_HDR) return -1;
    uint8_t op = blob[pos];
    uint32_t klen, vlen;
    memcpy(&klen, blob + pos + 1, 4);
    memcpy(&vlen, blob + pos + 5, 4);
    uint64_t body = uint64_t(klen) + vlen;
    if (body > len - pos - REC_HDR) return -1;
    if (op == OP_PUT) {
      if (deleting) return -1;
    } else if (op == OP_DEL) {
      if (vlen) return -1;
      deleting = true;
    } else {
      return -1;
    }
    uint64_t total = REC_V2_HDR + ns_len + body;
    const uint8_t *key = blob + pos + REC_HDR;
    if (out) {
      if (n >= counts[0] || w + total > counts[2]) return -2;
      uint8_t *r = out + w;
      put_u32(r + 4, seq0 + uint32_t(n));
      r[8] = op;
      put_u32(r + 9, ns_len + klen);
      put_u32(r + 13, vlen);
      if (ns_len) memcpy(r + REC_V2_HDR, ns, ns_len);
      memcpy(r + REC_V2_HDR + ns_len, key, body);
      put_u32(r, crc32(r + 4, total - 4));
      memcpy(keys + kw, r + REC_V2_HDR, ns_len + klen);
      klens[n] = int32_t(ns_len + klen);
      if (op == OP_PUT) {
        memcpy(vals + vw, key + klen, vlen);
        vlens[puts] = int32_t(vlen);
      }
    }
    w += total;
    kw += ns_len + klen;
    if (op == OP_PUT) {
      vw += vlen;
      ++puts;
    }
    pos += REC_HDR + body;
    ++n;
  }
  if (!out) {
    counts[0] = n;
    counts[1] = puts;
    counts[2] = w;
    counts[3] = kw;
    counts[4] = vw;
  } else if (n != counts[0] || w != counts[2]) {
    return -2;
  }
  return 0;
}

// Serialize every (key, value) with key starting with prefix, in key order,
// as klen(u32le) vlen(u32le) key value records.  Free with kv_buf_free.
int kv_scan_prefix(void* h, const char* prefix, uint32_t plen, char** out,
                   uint64_t* outlen) {
  auto* s = static_cast<Store*>(h);
  std::string pfx(prefix, plen);
  std::string buf;
  for (auto it = s->data.lower_bound(pfx); it != s->data.end(); ++it) {
    if (it->first.compare(0, pfx.size(), pfx) != 0) break;
    uint32_t klen = static_cast<uint32_t>(it->first.size());
    uint32_t vlen = static_cast<uint32_t>(it->second.size());
    char hdr[8];
    memcpy(hdr, &klen, 4);
    memcpy(hdr + 4, &vlen, 4);
    buf.append(hdr, 8);
    buf.append(it->first);
    buf.append(it->second);
  }
  *outlen = buf.size();
  *out = static_cast<char*>(malloc(buf.size() ? buf.size() : 1));
  memcpy(*out, buf.data(), buf.size());
  return 0;
}

int kv_compact(void* h) {
  return static_cast<Store*>(h)->compact() ? 0 : -1;
}

uint64_t kv_count(void* h) { return static_cast<Store*>(h)->data.size(); }

void kv_buf_free(char* p) { free(p); }

}  // extern "C"
