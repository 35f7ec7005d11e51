// Native data loader for swiftmpi_tpu: tokenization, vocab counting, and
// CBOW batch assembly.
//
// TPU-native equivalent of the reference's C++ host-side input machinery —
// LineFileReader + split + multithreaded gather_keys scans
// (/root/reference/src/utils/string.h:91-120, src/utils/file.h:14-33,
// src/apps/word2vec/word2vec.h:323-377) — feeding the device input pipeline
// instead of a ZMQ parameter server.  Exposed as a C ABI for ctypes; the
// Python fallback (swiftmpi_tpu/data/text.py) implements identical
// semantics:
//   * key modes: 0 = atoi with BKDR fallback (sync variant, hash_fn2),
//                1 = BKDR-13131 over uint32 (async variant, hash_fn)
//   * vocab ordered by (count desc, key asc) — matches data/text.py
//   * CBOW windows with per-position random shrink b in [0, W)
//     (word2vec.h:555) and center-only subsampling (word2vec.h:561)
//
// Build: g++ -O3 -std=c++17 -shared -fPIC loader.cpp -o libsmtpu_loader.so

#include <algorithm>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <mutex>
#include <random>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

inline uint64_t bkdr32(const char* s, size_t n) {
  uint32_t h = 0;
  for (size_t i = 0; i < n; i++) h = h * 13131u + (unsigned char)s[i];
  return (uint64_t)h;
}

inline uint64_t token_key(const char* s, size_t n, int mode) {
  if (mode == 0) {
    // atoi semantics with BKDR fallback for non-numeric tokens
    char* end = nullptr;
    std::string tmp(s, n);
    long long v = strtoll(tmp.c_str(), &end, 10);
    if (end && *end == '\0' && end != tmp.c_str()) return (uint64_t)v;
    return bkdr32(s, n);
  }
  return bkdr32(s, n);
}

struct Corpus {
  std::vector<int32_t> tokens;    // vocab indices, flattened
  std::vector<int64_t> offsets;   // sentence i = tokens[offsets[i]..offsets[i+1])
};

}  // namespace

extern "C" {

struct SmtpuVocab {
  std::vector<uint64_t> keys;
  std::vector<int64_t> counts;
  std::unordered_map<uint64_t, int32_t> index;
};

struct SmtpuCorpus {
  Corpus c;
};

// ---- vocab ----------------------------------------------------------------

// Counts apply the same sentence filtering as smtpu_corpus_map (length-
// filtered chunks), so vocab and corpus — and the python pipeline, which
// filters in load_corpus before build_vocab — stay consistent.
SmtpuVocab* smtpu_vocab_build(const char* path, int mode, int64_t min_count,
                              int64_t min_sentence_length,
                              int64_t max_sentence_length) {
  FILE* f = fopen(path, "rb");
  if (!f) return nullptr;
  std::unordered_map<uint64_t, int64_t> counts;
  std::vector<uint64_t> sent;
  char* line = nullptr;
  size_t cap = 0;
  ssize_t len;
  auto count_chunks = [&]() {
    for (size_t i = 0; i < sent.size(); i += (size_t)max_sentence_length) {
      size_t n = std::min((size_t)max_sentence_length, sent.size() - i);
      if ((int64_t)n < min_sentence_length) continue;
      for (size_t j = i; j < i + n; j++) counts[sent[j]]++;
    }
    sent.clear();
  };
  while ((len = getline(&line, &cap, f)) != -1) {
    char* p = line;
    char* end = line + len;
    sent.clear();
    while (p < end) {
      while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
        p++;
      char* start = p;
      while (p < end && *p != ' ' && *p != '\t' && *p != '\n' && *p != '\r')
        p++;
      if (p > start) sent.push_back(token_key(start, p - start, mode));
    }
    count_chunks();
  }
  free(line);
  fclose(f);

  auto* v = new SmtpuVocab();
  std::vector<std::pair<uint64_t, int64_t>> items;
  items.reserve(counts.size());
  for (auto& kv : counts)
    if (kv.second >= min_count) items.push_back(kv);
  std::sort(items.begin(), items.end(), [](const auto& a, const auto& b) {
    return a.second != b.second ? a.second > b.second : a.first < b.first;
  });
  v->keys.reserve(items.size());
  v->counts.reserve(items.size());
  for (size_t i = 0; i < items.size(); i++) {
    v->keys.push_back(items[i].first);
    v->counts.push_back(items[i].second);
    v->index.emplace(items[i].first, (int32_t)i);
  }
  return v;
}

int64_t smtpu_vocab_size(const SmtpuVocab* v) { return (int64_t)v->keys.size(); }

void smtpu_vocab_copy(const SmtpuVocab* v, uint64_t* keys, int64_t* counts) {
  memcpy(keys, v->keys.data(), v->keys.size() * sizeof(uint64_t));
  memcpy(counts, v->counts.data(), v->counts.size() * sizeof(int64_t));
}

void smtpu_vocab_free(SmtpuVocab* v) { delete v; }

// ---- corpus mapping -------------------------------------------------------

SmtpuCorpus* smtpu_corpus_map(const char* path, int mode,
                              const SmtpuVocab* v,
                              int64_t min_sentence_length,
                              int64_t max_sentence_length) {
  FILE* f = fopen(path, "rb");
  if (!f) return nullptr;
  auto* out = new SmtpuCorpus();
  out->c.offsets.push_back(0);
  std::vector<int32_t> sent;
  char* line = nullptr;
  size_t cap = 0;
  ssize_t len;
  auto flush_chunks = [&](std::vector<int32_t>& s) {
    for (size_t i = 0; i < s.size(); i += (size_t)max_sentence_length) {
      size_t n = std::min((size_t)max_sentence_length, s.size() - i);
      if ((int64_t)n < min_sentence_length) continue;
      out->c.tokens.insert(out->c.tokens.end(), s.begin() + i,
                           s.begin() + i + n);
      out->c.offsets.push_back((int64_t)out->c.tokens.size());
    }
    s.clear();
  };
  while ((len = getline(&line, &cap, f)) != -1) {
    char* p = line;
    char* end = line + len;
    sent.clear();
    while (p < end) {
      while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
        p++;
      char* start = p;
      while (p < end && *p != ' ' && *p != '\t' && *p != '\n' && *p != '\r')
        p++;
      if (p > start) {
        auto it = v->index.find(token_key(start, p - start, mode));
        if (it != v->index.end()) sent.push_back(it->second);
      }
    }
    flush_chunks(sent);
  }
  free(line);
  fclose(f);
  return out;
}

int64_t smtpu_corpus_n_sentences(const SmtpuCorpus* c) {
  return (int64_t)c->c.offsets.size() - 1;
}
int64_t smtpu_corpus_n_tokens(const SmtpuCorpus* c) {
  return (int64_t)c->c.tokens.size();
}
void smtpu_corpus_copy(const SmtpuCorpus* c, int32_t* tokens,
                       int64_t* offsets) {
  memcpy(tokens, c->c.tokens.data(), c->c.tokens.size() * sizeof(int32_t));
  memcpy(offsets, c->c.offsets.data(),
         c->c.offsets.size() * sizeof(int64_t));
}
void smtpu_corpus_free(SmtpuCorpus* c) { delete c; }

// ---- CBOW batcher ---------------------------------------------------------

struct SmtpuBatcher {
  const int32_t* tokens;   // borrowed (numpy-owned) buffers
  const int64_t* offsets;
  int64_t n_sents;
  int window;
  const float* keep_prob;  // per vocab index; nullptr = no subsampling
  std::mt19937_64 rng;
  std::vector<int64_t> order;   // sentence permutation for this epoch
  int64_t sent_i;               // position in `order`
  int64_t pos_i;                // position within current sentence
  int pending_half;             // stencil: drawn-but-unadmitted center's
                                // half-window (-1 = none); preserves the
                                // rng stream across batch closes
};

SmtpuBatcher* smtpu_batcher_new(const int32_t* tokens, const int64_t* offsets,
                                int64_t n_sents, int window,
                                const float* keep_prob, uint64_t seed) {
  auto* b = new SmtpuBatcher();
  b->tokens = tokens;
  b->offsets = offsets;
  b->n_sents = n_sents;
  b->window = window;
  b->keep_prob = keep_prob;
  b->rng.seed(seed);
  b->order.resize(n_sents);
  for (int64_t i = 0; i < n_sents; i++) b->order[i] = i;
  std::shuffle(b->order.begin(), b->order.end(), b->rng);
  b->sent_i = 0;
  b->pos_i = 0;
  b->pending_half = -1;
  return b;
}

void smtpu_batcher_reset(SmtpuBatcher* b, uint64_t seed) {
  b->rng.seed(seed);
  std::shuffle(b->order.begin(), b->order.end(), b->rng);
  b->sent_i = 0;
  b->pos_i = 0;
  b->pending_half = -1;
}

// Fill up to batch_size examples; contexts/mask are (batch_size, 2*window).
// Returns the number of examples produced; 0 means the epoch is exhausted.
int64_t smtpu_batcher_next(SmtpuBatcher* b, int64_t batch_size,
                           int32_t* centers, int32_t* contexts,
                           uint8_t* mask) {
  const int W = b->window;
  const int W2 = 2 * W;
  std::uniform_real_distribution<float> unif(0.0f, 1.0f);
  int64_t filled = 0;
  memset(contexts, 0, (size_t)batch_size * W2 * sizeof(int32_t));
  memset(mask, 0, (size_t)batch_size * W2);
  while (filled < batch_size && b->sent_i < b->n_sents) {
    int64_t s = b->order[b->sent_i];
    const int32_t* sent = b->tokens + b->offsets[s];
    int64_t L = b->offsets[s + 1] - b->offsets[s];
    for (; b->pos_i < L && filled < batch_size; b->pos_i++) {
      int64_t pos = b->pos_i;
      // center-only subsample gate (word2vec.h:561)
      if (b->keep_prob &&
          unif(b->rng) >= b->keep_prob[sent[pos]])
        continue;
      int bshrink = (int)(b->rng() % (uint64_t)W);   // word2vec.h:555
      int half = W - bshrink;
      int64_t lo = pos - half < 0 ? 0 : pos - half;
      int64_t hi = pos + half + 1 > L ? L : pos + half + 1;
      int n_ctx = 0;
      int32_t* ctx_row = contexts + filled * W2;
      uint8_t* m_row = mask + filled * W2;
      for (int64_t c = lo; c < hi; c++) {
        if (c == pos) continue;
        ctx_row[n_ctx] = sent[c];
        m_row[n_ctx] = 1;
        n_ctx++;
      }
      if (n_ctx == 0) {
        memset(ctx_row, 0, W2 * sizeof(int32_t));
        memset(m_row, 0, W2);
        continue;
      }
      centers[filled] = sent[pos];
      filled++;
    }
    if (b->pos_i >= L) {
      b->sent_i++;
      b->pos_i = 0;
    }
  }
  return filled;
}

void smtpu_batcher_free(SmtpuBatcher* b) { delete b; }

// ---- positional-stencil batcher -------------------------------------------
//
// Emits stream spans instead of per-pair rows: `tokens`/`sent_id` hold a
// contiguous slice of the shuffled sentence stream (capacity S = `span`
// positions, sized by the caller to hold batch_size centers under the
// center gate: data/text.py span_positions; at least batch_size +
// 2*window), `center_pos`/`half` index into it.  A batch closes at
// batch_size centers; one that fills the span first closes short.
// Expansion semantics match data/text.py's stencil_to_cbow; the
// rng is consumed in exactly smtpu_batcher_next's per-position order (keep
// coin, then shrink only if kept), so the expanded pair stream for a seed
// equals the per-pair epoch's.  Do not interleave per-pair and stencil
// calls on one batcher without a reset: they share the walk cursors.
//
// Output buffers: tokens (S,) int32, sent_id (S,) int32 (-1 = padding),
// center_pos (batch_size,) int32 (-1 = padding), half (batch_size,) int32.
// Returns admitted center count; 0 = epoch exhausted.
int64_t smtpu_batcher_next_span(SmtpuBatcher* b, int64_t batch_size,
                                int64_t span, int32_t* tokens,
                                int32_t* sent_id, int32_t* center_pos,
                                int32_t* half) {
  const int W = b->window;
  const int64_t S = span;
  std::uniform_real_distribution<float> unif(0.0f, 1.0f);
  for (int64_t i = 0; i < S; i++) { tokens[i] = 0; sent_id[i] = -1; }
  for (int64_t i = 0; i < batch_size; i++) {
    center_pos[i] = -1;
    half[i] = 0;
  }
  int64_t fill = 0;   // span rows used
  int64_t nc = 0;     // centers admitted
  int32_t ns = 0;     // batch-local sentence counter
  while (b->sent_i < b->n_sents) {
    int64_t s = b->order[b->sent_i];
    const int32_t* sent = b->tokens + b->offsets[s];
    int64_t L = b->offsets[s + 1] - b->offsets[s];
    int64_t p = b->pos_i;
    int64_t p0 = 0;       // first sentence position resident in the span
    int64_t base = fill;  // span index of sentence position p0
    int64_t have = 0;     // positions [p0, p0+have) are appended
    int32_t sid = ns++;
    if (p > 0) {
      // mid-sentence resume (only at call start, fill == 0): replay the
      // left tail so upcoming centers keep their left context
      p0 = p - W > 0 ? p - W : 0;
      base = fill;
      for (int64_t k = 0; k < p - p0; k++) {
        tokens[fill + k] = sent[p0 + k];
        sent_id[fill + k] = sid;
      }
      fill += p - p0;
      have = p - p0;
    }
    for (; p < L; p++) {
      int hf;
      if (b->pending_half >= 0) {
        hf = b->pending_half;       // drawn before the previous close
        b->pending_half = -1;
      } else {
        // center-only subsample gate, then shrink (word2vec.h:555,561)
        if (b->keep_prob && unif(b->rng) >= b->keep_prob[sent[p]]) continue;
        hf = W - (int)(b->rng() % (uint64_t)W);
      }
      int64_t left = hf < p ? hf : p;
      int64_t right = hf < L - 1 - p ? hf : L - 1 - p;
      if (left + right == 0) continue;
      if (have == 0 && p - W > p0) p0 = p - W;  // skip unreachable prefix
      int64_t end = p + right;  // last sentence position this window needs
      if (nc == batch_size || base + (end - p0) >= S) {
        b->pending_half = hf;   // re-admit p in the next span
        b->pos_i = p;
        return nc;
      }
      if (end - p0 >= have) {   // append contiguously through the window
        int64_t n_new = end - p0 + 1 - have;
        for (int64_t k = 0; k < n_new; k++) {
          tokens[fill + k] = sent[p0 + have + k];
          sent_id[fill + k] = sid;
        }
        fill += n_new;
        have += n_new;
      }
      center_pos[nc] = (int32_t)(base + (p - p0));
      half[nc] = (int32_t)hf;
      nc++;
    }
    b->sent_i++;
    b->pos_i = 0;
  }
  return nc;
}

// ---- prefetch executor ----------------------------------------------------
//
// Background batch-assembly pipeline: a producer thread drives the batcher
// through one epoch while the device computes — the TPU-native role of the
// reference's AsynExec thread pool + BasicChannel task queue
// (/root/reference/src/utils/AsynExec.h:34-51, BasicChannel.h), repurposed
// from RPC-handler fan-out to input-pipeline overlap.  Bounded queue depth
// gives backpressure exactly like queue_with_capacity (utils/queue.h:50-114).

struct SmtpuPrefetcher {
  struct Item {
    std::vector<int32_t> centers;
    std::vector<int32_t> contexts;
    std::vector<uint8_t> mask;
    int64_t n;
  };
  SmtpuBatcher* b;   // borrowed; caller keeps it alive
  int64_t batch_size;
  size_t depth;
  std::thread producer;
  std::mutex mu;
  std::condition_variable cv_push, cv_pop;
  std::deque<Item> q;
  bool done = false;       // producer finished the epoch
  bool cancel = false;     // consumer is shutting down

  void run() {
    const int W2 = 2 * b->window;
    for (;;) {
      Item it;
      it.centers.resize(batch_size);
      it.contexts.resize(batch_size * W2);
      it.mask.resize(batch_size * W2);
      it.n = smtpu_batcher_next(b, batch_size, it.centers.data(),
                                it.contexts.data(), it.mask.data());
      std::unique_lock<std::mutex> lk(mu);
      if (it.n == 0) break;
      cv_push.wait(lk, [&] { return q.size() < depth || cancel; });
      if (cancel) return;
      bool last = it.n < batch_size;
      q.push_back(std::move(it));
      cv_pop.notify_one();
      if (last) break;
    }
    std::lock_guard<std::mutex> lk(mu);
    done = true;
    cv_pop.notify_one();
  }
};

SmtpuPrefetcher* smtpu_prefetcher_new(SmtpuBatcher* b, int64_t batch_size,
                                      int64_t depth, uint64_t epoch_seed) {
  smtpu_batcher_reset(b, epoch_seed);
  auto* p = new SmtpuPrefetcher();
  p->b = b;
  p->batch_size = batch_size;
  p->depth = (size_t)(depth < 1 ? 1 : depth);
  p->producer = std::thread([p] { p->run(); });
  return p;
}

// Blocks until a batch is ready; returns n examples (0 = epoch exhausted).
int64_t smtpu_prefetcher_next(SmtpuPrefetcher* p, int32_t* centers,
                              int32_t* contexts, uint8_t* mask) {
  std::unique_lock<std::mutex> lk(p->mu);
  p->cv_pop.wait(lk, [&] { return !p->q.empty() || p->done; });
  if (p->q.empty()) return 0;
  SmtpuPrefetcher::Item it = std::move(p->q.front());
  p->q.pop_front();
  p->cv_push.notify_one();
  lk.unlock();
  const int W2 = 2 * p->b->window;
  memcpy(centers, it.centers.data(), p->batch_size * sizeof(int32_t));
  memcpy(contexts, it.contexts.data(),
         p->batch_size * W2 * sizeof(int32_t));
  memcpy(mask, it.mask.data(), p->batch_size * W2);
  return it.n;
}

void smtpu_prefetcher_free(SmtpuPrefetcher* p) {
  {
    std::lock_guard<std::mutex> lk(p->mu);
    p->cancel = true;
    p->cv_push.notify_all();
  }
  if (p->producer.joinable()) p->producer.join();
  delete p;
}

}  // extern "C"
