// Native IO for swiftmpi_tpu: libSVM parsing and text-checkpoint read/write.
//
// TPU-native equivalents of the reference's native IO paths:
//   * libSVM instance parsing — parse_instance2's strtol/strtod scan
//     (/root/reference/src/apps/logistic/lr.cpp:103-131), here one pass over
//     the whole file into CSR-style arrays ready for numpy.
//   * text checkpoint out/in — SparseTable::output's "key\tvalue" line dump
//     (/root/reference/src/parameter/sparsetable.h:119-132) and
//     ClusterServer::load's line scan (src/cluster/server.h:49-62); value
//     layout is N float32 fields separated by tabs, each a space-joined
//     vector (the word2vec WParam operator<< shape, word2vec.h:100-110).
//
// Exposed as a C ABI for ctypes (same .so as loader.cpp).  %.9g printing
// round-trips float32 exactly; parsing uses strtof/strtoull.

#include <cctype>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <vector>

extern "C" {

// ---- libSVM ---------------------------------------------------------------

struct SmtpuLibsvm {
  std::vector<float> labels;       // (N,) already mapped {-1,+1}/{0,1} -> {0,1}
  std::vector<int64_t> offsets;    // (N+1,) feature-range of row i
  std::vector<uint64_t> feat_ids;  // (nnz,)
  std::vector<float> feat_vals;    // (nnz,)
  int64_t n_bad = 0;               // malformed lines (python parser raises)
};

// Parse a whole libSVM file: "label id:val id:val ... [# comment]".
// Semantics match the python fallback (data/libsvm.py parse_line/load_file):
// blank lines and '#' lines are skipped, trailing '#' comments end the row,
// feature-less rows are dropped, labels <= 0 map to 0.  Malformed lines
// (unparsable label or a feature token that is not id:val) are counted in
// n_bad — the python binding raises if any, as the python parser would.
SmtpuLibsvm* smtpu_libsvm_parse(const char* path) {
  FILE* f = fopen(path, "rb");
  if (!f) return nullptr;
  auto* out = new SmtpuLibsvm();
  out->offsets.push_back(0);
  char* line = nullptr;
  size_t cap = 0;
  ssize_t len;
  while ((len = getline(&line, &cap, f)) != -1) {
    char* p = line;
    while (*p == ' ' || *p == '\t') p++;
    if (*p == '\0' || *p == '\n' || *p == '#') continue;
    char* end = nullptr;
    float label = strtof(p, &end);
    if (end == p) {  // unparsable label (python: ValueError)
      out->n_bad++;
      continue;
    }
    p = end;
    size_t row_start = out->feat_ids.size();
    bool bad = false;
    while (*p) {
      while (*p == ' ' || *p == '\t') p++;
      if (*p == '\0' || *p == '\n' || *p == '\r' || *p == '#') break;
      uint64_t fid = strtoull(p, &end, 10);
      if (end == p || *end != ':') { bad = true; break; }
      p = end + 1;
      float fval = strtof(p, &end);
      if (end == p) { bad = true; break; }
      p = end;
      out->feat_ids.push_back(fid);
      out->feat_vals.push_back(fval);
    }
    if (bad) {  // python raises on e.g. "1 abc 3:1"; never keep partial rows
      out->feat_ids.resize(row_start);
      out->feat_vals.resize(row_start);
      out->n_bad++;
      continue;
    }
    if (out->feat_ids.size() == row_start)  // feature-less row: dropped
      continue;                             // (load_file's `ins[1]` filter)
    out->labels.push_back(label > 0 ? 1.0f : 0.0f);
    out->offsets.push_back((int64_t)out->feat_ids.size());
  }
  free(line);
  fclose(f);
  return out;
}

int64_t smtpu_libsvm_n_bad(const SmtpuLibsvm* d) { return d->n_bad; }

int64_t smtpu_libsvm_n_rows(const SmtpuLibsvm* d) {
  return (int64_t)d->labels.size();
}
int64_t smtpu_libsvm_nnz(const SmtpuLibsvm* d) {
  return (int64_t)d->feat_ids.size();
}
void smtpu_libsvm_copy(const SmtpuLibsvm* d, float* labels, int64_t* offsets,
                       uint64_t* feat_ids, float* feat_vals) {
  memcpy(labels, d->labels.data(), d->labels.size() * sizeof(float));
  memcpy(offsets, d->offsets.data(), d->offsets.size() * sizeof(int64_t));
  memcpy(feat_ids, d->feat_ids.data(),
         d->feat_ids.size() * sizeof(uint64_t));
  memcpy(feat_vals, d->feat_vals.data(),
         d->feat_vals.size() * sizeof(float));
}
void smtpu_libsvm_free(SmtpuLibsvm* d) { delete d; }

// ---- text checkpoint write ------------------------------------------------

// Write n_rows lines "key\tfield0\tfield1..." where field j is dims[j]
// space-joined %.9g floats read from fields[j] (row-major (n_rows, dims[j])).
// Returns rows written, or -1 with errno set when the file cannot be
// opened or a write fails (a full disk must not leave a short file that
// looks saved).
int64_t smtpu_dump_rows(const char* path, const uint64_t* keys,
                        int64_t n_rows, int64_t n_fields,
                        const float* const* fields, const int64_t* dims) {
  FILE* f = fopen(path, "wb");
  if (!f) return -1;
  std::vector<char> buf(1 << 20);
  setvbuf(f, buf.data(), _IOFBF, buf.size());
  for (int64_t r = 0; r < n_rows && !ferror(f); r++) {
    fprintf(f, "%llu", (unsigned long long)keys[r]);
    for (int64_t j = 0; j < n_fields; j++) {
      fputc('\t', f);
      const float* row = fields[j] + r * dims[j];
      for (int64_t k = 0; k < dims[j]; k++) {
        if (k) fputc(' ', f);
        fprintf(f, "%.9g", (double)row[k]);
      }
    }
    fputc('\n', f);
  }
  // stdio keeps the failed write's errno only until the next call
  bool failed = ferror(f) != 0;
  int err = errno;
  if (fclose(f) != 0 && !failed) {
    failed = true;
    err = errno;
  }
  if (failed) {
    errno = err;
    return -1;
  }
  return n_rows;
}

// ---- text checkpoint read -------------------------------------------------

struct SmtpuTextTable {
  std::vector<uint64_t> keys;
  std::vector<std::vector<float>> fields;  // field j: (n_rows * dims[j])
  std::vector<int64_t> dims;
};

// Parse "key\tfield\tfield..." lines; every row must provide exactly
// dims[j] floats per field (rows with a wrong count are skipped).
SmtpuTextTable* smtpu_load_rows(const char* path, int64_t n_fields,
                                const int64_t* dims) {
  FILE* f = fopen(path, "rb");
  if (!f) return nullptr;
  auto* out = new SmtpuTextTable();
  out->fields.resize(n_fields);
  out->dims.assign(dims, dims + n_fields);
  char* line = nullptr;
  size_t cap = 0;
  ssize_t len;
  std::vector<float> tmp;
  while ((len = getline(&line, &cap, f)) != -1) {
    char* p = line;
    while (*p == ' ') p++;
    if (*p == '\0' || *p == '\n') continue;
    char* end = nullptr;
    uint64_t key = strtoull(p, &end, 10);
    if (end == p) continue;
    p = end;
    tmp.clear();
    bool ok = true;
    int64_t expect = 0;
    for (int64_t j = 0; j < n_fields; j++) expect += dims[j];
    while (*p && *p != '\n') {
      while (*p == ' ' || *p == '\t') p++;
      if (*p == '\0' || *p == '\n' || *p == '\r') break;
      float v = strtof(p, &end);
      if (end == p) { ok = false; break; }
      tmp.push_back(v);
      p = end;
    }
    if (!ok || (int64_t)tmp.size() != expect) continue;
    out->keys.push_back(key);
    int64_t at = 0;
    for (int64_t j = 0; j < n_fields; j++) {
      out->fields[j].insert(out->fields[j].end(), tmp.begin() + at,
                            tmp.begin() + at + dims[j]);
      at += dims[j];
    }
  }
  free(line);
  fclose(f);
  return out;
}

int64_t smtpu_text_n_rows(const SmtpuTextTable* t) {
  return (int64_t)t->keys.size();
}
void smtpu_text_copy(const SmtpuTextTable* t, uint64_t* keys,
                     float* const* fields) {
  memcpy(keys, t->keys.data(), t->keys.size() * sizeof(uint64_t));
  for (size_t j = 0; j < t->fields.size(); j++)
    memcpy(fields[j], t->fields[j].data(),
           t->fields[j].size() * sizeof(float));
}
void smtpu_text_free(SmtpuTextTable* t) { delete t; }

}  // extern "C"
