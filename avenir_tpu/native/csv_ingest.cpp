// Fast columnar CSV ingest for avenir_tpu.
//
// The reference's ingest is the Hadoop InputFormat + per-mapper
// line.split() (e.g. bayesian/BayesianDistribution.java:137); the TPU
// framework replaces HDFS splits with host CSV -> device arrays, and this
// library makes that host step native: one pass over the byte buffer
// producing float32 numeric columns and dictionary-encoded int32
// categorical columns directly (no Python string objects per field). A
// categorical whose vocabulary the schema does not declare is encoded the
// same way: csv_distinct_column hands back the column's distinct tokens,
// the caller settles the vocabulary from that handful, and the parse
// encodes the column beside the others.
//
// Exposed via ctypes (no pybind11 in the image); see
// avenir_tpu/native/ingest.py for the Python contract.

#include <fcntl.h>
#include <unistd.h>

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

namespace {

// Trim ASCII whitespace in [b, e).
inline void trim(const char*& b, const char*& e) {
    while (b < e && (*b == ' ' || *b == '\t' || *b == '\r')) ++b;
    while (e > b && (e[-1] == ' ' || e[-1] == '\t' || e[-1] == '\r')) --e;
}

// Allocation-free categorical vocabulary: open-addressing over the value
// list, probed with (ptr, len) so the hot loop never constructs a
// std::string per token (the former unordered_map<string> lookup was the
// parse-rate bottleneck together with strtof).
struct Vocab {
    std::vector<std::string> values;
    std::vector<int32_t> slots;   // open addressing, -1 empty
    size_t mask = 0;

    static uint64_t hash(const char* b, size_t n) {
        uint64_t h = 1469598103934665603ull;          // FNV-1a
        for (size_t i = 0; i < n; ++i) {
            h ^= static_cast<unsigned char>(b[i]);
            h *= 1099511628211ull;
        }
        return h;
    }

    void place(size_t v) {
        size_t h = hash(values[v].data(), values[v].size()) & mask;
        while (slots[h] >= 0) h = (h + 1) & mask;
        slots[h] = static_cast<int32_t>(v);
    }

    void build() {
        size_t cap = 8;
        while (cap < values.size() * 2) cap <<= 1;
        slots.assign(cap, -1);
        mask = cap - 1;
        for (size_t v = 0; v < values.size(); ++v) place(v);
    }

    // Discovery: a token not seen yet takes the next code. Only a new
    // token is copied, so a column of few values allocates a few times.
    void add(const char* b, size_t n) {
        if (slots.empty()) build();
        if (find(b, n) >= 0) return;
        values.emplace_back(b, n);
        if (values.size() * 2 > slots.size()) build();
        else place(values.size() - 1);
    }

    int32_t find(const char* b, size_t n) const {
        size_t h = hash(b, n) & mask;
        while (slots[h] >= 0) {
            const std::string& s = values[slots[h]];
            if (s.size() == n && memcmp(s.data(), b, n) == 0) return slots[h];
            h = (h + 1) & mask;
        }
        return -1;
    }
};

const double kPow10[10] = {1e0, 1e1, 1e2, 1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9};

// Fast path for plain [+-]digits[.digits] tokens (the overwhelming CSV
// case); returns false for exponents/specials so the caller can fall back
// to strtof.
inline bool parse_float_fast(const char* b, const char* e, float* out) {
    bool neg = false;
    const char* p = b;
    if (p < e && (*p == '-' || *p == '+')) { neg = *p == '-'; ++p; }
    int64_t ip = 0;
    int nd = 0;
    while (p < e && *p >= '0' && *p <= '9') {
        if (nd == 18) return false;   // before the multiply: no signed overflow
        ip = ip * 10 + (*p - '0');
        ++p;
        ++nd;
    }
    if (nd == 0) return false;
    double v;
    if (p == e) {
        v = static_cast<double>(ip);
    } else {
        if (*p != '.') return false;
        ++p;
        int64_t fp = 0;
        int fd = 0;
        while (p < e && *p >= '0' && *p <= '9') {
            fp = fp * 10 + (*p - '0');
            ++p;
            if (++fd > 9) return false;
        }
        if (p != e) return false;
        v = static_cast<double>(ip) + static_cast<double>(fp) / kPow10[fd];
    }
    *out = static_cast<float>(neg ? -v : v);
    return true;
}

// Shared per-parse lookup tables (built once, read-only across threads).
struct ParseTables {
    std::vector<int8_t> kind;     // ordinal -> 0 none, 1 numeric, 2 cat
    std::vector<int32_t> slot;
    std::vector<Vocab> vocabs;
    int32_t max_ord;
};

// Parse rows in [p, end) writing global rows [row_base, row_base+max_rows).
// Returns rows parsed, or -1 (unknown categorical) / -2 (bad numeric) with
// err_row (global) / err_ord set.
int64_t parse_range(const char* p, const char* end, char delim,
                    const ParseTables& t, float* num_out, int32_t* cat_out,
                    int64_t n_rows, int64_t row_base, int64_t max_rows,
                    int64_t* err_row, int32_t* err_ord) {
    int64_t row = 0;
    while (p < end && row < max_rows) {
        const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
        const char* line_end = nl ? nl : end;
        {
            const char* b = p;
            const char* e = line_end;
            trim(b, e);
            if (e <= b) {  // blank line
                p = nl ? nl + 1 : end;
                continue;
            }
        }
        int32_t ord = 0;
        const char* fb = p;
        for (const char* q = p; q <= line_end; ++q) {
            if (q == line_end || *q == delim) {
                if (ord <= t.max_ord && t.kind[ord]) {
                    const char* b = fb;
                    const char* e = q;
                    trim(b, e);
                    if (t.kind[ord] == 1) {
                        float v;
                        if (e == b) {
                            v = __builtin_nanf("");
                        } else if (!parse_float_fast(b, e, &v)) {
                            // exponents/specials: fall back to strtof
                            char* endp = nullptr;
                            std::string tok(b, e - b);
                            v = strtof(tok.c_str(), &endp);
                            if (endp == tok.c_str() || *endp != '\0') {
                                *err_row = row_base + row;
                                *err_ord = ord;
                                return -2;
                            }
                        }
                        num_out[static_cast<int64_t>(t.slot[ord]) * n_rows
                                + row_base + row] = v;
                    } else {
                        int32_t code = t.vocabs[t.slot[ord]].find(b, e - b);
                        if (code < 0) {
                            *err_row = row_base + row;
                            *err_ord = ord;
                            return -1;
                        }
                        cat_out[static_cast<int64_t>(t.slot[ord]) * n_rows
                                + row_base + row] = code;
                    }
                }
                ++ord;
                fb = q + 1;
            }
        }
        ++row;
        p = nl ? nl + 1 : end;
    }
    return row;
}

ParseTables build_tables(int32_t max_ord, const int32_t* num_ords,
                         int32_t n_num, const int32_t* cat_ords,
                         int32_t n_cat, const char* vocab_blob,
                         const int32_t* vocab_counts) {
    ParseTables t;
    t.max_ord = max_ord;
    t.kind.assign(max_ord + 1, 0);
    t.slot.assign(max_ord + 1, -1);
    for (int32_t i = 0; i < n_num; ++i) {
        t.kind[num_ords[i]] = 1;
        t.slot[num_ords[i]] = i;
    }
    t.vocabs.resize(n_cat);
    const char* vp = vocab_blob;
    for (int32_t c = 0; c < n_cat; ++c) {
        t.kind[cat_ords[c]] = 2;
        t.slot[cat_ords[c]] = c;
        for (int32_t v = 0; v < vocab_counts[c]; ++v) {
            t.vocabs[c].values.emplace_back(vp);
            vp += strlen(vp) + 1;
        }
        t.vocabs[c].build();
    }
    return t;
}

// Count non-empty rows in [p, end).
int64_t count_range(const char* p, const char* end) {
    int64_t rows = 0;
    while (p < end) {
        const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
        const char* line_end = nl ? nl : end;
        const char* b = p;
        const char* e = line_end;
        trim(b, e);
        if (e > b) ++rows;
        p = nl ? nl + 1 : end;
    }
    return rows;
}

// Stripe [buf, buf+len) into n newline-aligned ranges; bounds[i..i+1]
// delimits stripe i.
std::vector<const char*> stripe_bounds(const char* buf, int64_t len,
                                       int32_t n) {
    std::vector<const char*> bounds(n + 1);
    bounds[0] = buf;
    bounds[n] = buf + len;
    for (int32_t i = 1; i < n; ++i) {
        const char* p = buf + len * i / n;
        const char* nl = static_cast<const char*>(
            memchr(p, '\n', buf + len - p));
        bounds[i] = nl ? nl + 1 : buf + len;
    }
    return bounds;
}

// Stripes worth spawning over len bytes for a caller asking n_threads
// (0: the host's cores): below ~4MB a stripe the spawn+count overhead
// beats the parallel win. At least 1.
int32_t stripe_count(int64_t len, int32_t n_threads) {
    if (n_threads <= 0)
        n_threads = static_cast<int32_t>(std::thread::hardware_concurrency());
    int64_t max_stripes = len / (4 << 20);
    if (n_threads > max_stripes) n_threads = static_cast<int32_t>(max_stripes);
    return n_threads < 1 ? 1 : n_threads;
}

// fn(b, e) on the trimmed token at `ordinal` of every non-blank line of
// [p, end), in row order; a short row gives the empty token, which keeps
// the rows aligned. Returns the rows visited.
template <typename Fn>
int64_t for_each_token(const char* p, const char* end, char delim,
                       int32_t ordinal, Fn fn) {
    int64_t rows = 0;
    while (p < end) {
        const char* nl = static_cast<const char*>(memchr(p, '\n', end - p));
        const char* line_end = nl ? nl : end;
        const char* b = p;
        const char* e = line_end;
        trim(b, e);
        if (e > b) {
            int32_t ord = 0;
            const char* fb = p;
            const char* q = p;
            for (; q < line_end; ++q) {
                if (*q == delim) {
                    if (ord == ordinal) break;
                    ++ord;
                    fb = q + 1;
                }
            }
            if (ord != ordinal) fb = q;  // short row
            trim(fb, q);
            fn(fb, q);
            ++rows;
        }
        p = nl ? nl + 1 : end;
    }
    return rows;
}

// Run fn(i) on n threads; false if spawning failed (work may be partially
// done — callers must treat false as "redo sequentially").
template <typename Fn>
bool run_threads(int32_t n, Fn fn) {
    std::vector<std::thread> ts;
    ts.reserve(n);
    try {
        for (int32_t i = 0; i < n; ++i) ts.emplace_back([fn, i] { fn(i); });
    } catch (...) {
        // std::system_error from thread creation (pid/memory limits):
        // join what started, report failure — throwing across the
        // extern "C" boundary would std::terminate the host process
        for (auto& th : ts) th.join();
        return false;
    }
    for (auto& th : ts) th.join();
    return true;
}

}  // namespace

extern "C" {

// Count non-empty lines.
int64_t csv_count_rows(const char* buf, int64_t len) {
    return count_range(buf, buf + len);
}

// Parse the buffer in one pass.
//
// num_ords / n_num: field ordinals to parse as float32 into num_out
//   (column-major: num_out[c * n_rows + r]); empty tokens -> NaN, invalid
//   non-empty tokens abort with -2 (see return doc).
// cat_ords / n_cat: field ordinals to dictionary-encode into cat_out
//   (column-major int32). The vocabulary for categorical column c is
//   vocab_blob[vocab_off[vc] .. ] holding vocab_counts[c] zero-terminated
//   strings back to back (vc = running string index). Unknown values
//   write -1 and the row/ordinal of the first failure into err_row/err_ord.
// String/id columns are extracted separately via csv_extract_column.
//
// Returns the number of parsed rows, -1 on unknown categorical value, or
// -2 on an invalid non-empty numeric token (err_row/err_ord locate it).
int64_t csv_parse(const char* buf, int64_t len, char delim, int32_t max_ord,
                  const int32_t* num_ords, int32_t n_num, float* num_out,
                  const int32_t* cat_ords, int32_t n_cat,
                  const char* vocab_blob, const int32_t* vocab_counts,
                  int32_t* cat_out, int64_t n_rows,
                  int64_t* err_row, int32_t* err_ord) {
    ParseTables t = build_tables(max_ord, num_ords, n_num, cat_ords, n_cat,
                                 vocab_blob, vocab_counts);
    return parse_range(buf, buf + len, delim, t, num_out, cat_out, n_rows,
                       0, n_rows, err_row, err_ord);
}

// Multi-threaded csv_parse: the buffer splits into `n_threads` stripes at
// newline boundaries; each stripe is row-counted, prefix-summed into a
// global row base, then parsed in parallel into the shared column-major
// outputs (disjoint row ranges, no synchronization needed). Semantics are
// identical to csv_parse; on error the failure with the LOWEST global row
// wins (matching the sequential first-failure contract). A v5e host has
// ~100 usable cores; the single-threaded parse rate (~2M rows/sec) is the
// streaming CSV path's bound, so this is where host ingest scales.
int64_t csv_parse_mt(const char* buf, int64_t len, char delim,
                     int32_t max_ord, const int32_t* num_ords, int32_t n_num,
                     float* num_out, const int32_t* cat_ords, int32_t n_cat,
                     const char* vocab_blob, const int32_t* vocab_counts,
                     int32_t* cat_out, int64_t n_rows,
                     int64_t* err_row, int32_t* err_ord, int32_t n_threads) {
    n_threads = stripe_count(len, n_threads);
    if (n_threads <= 1)
        return csv_parse(buf, len, delim, max_ord, num_ords, n_num, num_out,
                         cat_ords, n_cat, vocab_blob, vocab_counts, cat_out,
                         n_rows, err_row, err_ord);

    ParseTables t = build_tables(max_ord, num_ords, n_num, cat_ords, n_cat,
                                 vocab_blob, vocab_counts);
    std::vector<const char*> bounds = stripe_bounds(buf, len, n_threads);
    // pass A: parallel row count per stripe
    std::vector<int64_t> stripe_rows(n_threads, 0);
    bool ok = run_threads(n_threads, [&](int32_t i) {
        stripe_rows[i] = count_range(bounds[i], bounds[i + 1]);
    });
    std::vector<int64_t> base(n_threads + 1, 0);
    for (int32_t i = 0; i < n_threads; ++i)
        base[i + 1] = base[i] + stripe_rows[i];
    // thread-spawn failure or under-allocated output (the sequential
    // contract is "parse at most n_rows"): fall back to the sequential
    // path, which implements both cases exactly
    if (!ok || base[n_threads] > n_rows)
        return parse_range(buf, buf + len, delim, t, num_out, cat_out,
                           n_rows, 0, n_rows, err_row, err_ord);

    // pass B: parallel parse into disjoint global row ranges
    std::vector<int64_t> st(n_threads, 0), erow(n_threads, -1);
    std::vector<int32_t> eord(n_threads, -1);
    ok = run_threads(n_threads, [&](int32_t i) {
        st[i] = parse_range(bounds[i], bounds[i + 1], delim, t,
                            num_out, cat_out, n_rows, base[i],
                            stripe_rows[i], &erow[i], &eord[i]);
    });
    if (!ok)
        return parse_range(buf, buf + len, delim, t, num_out, cat_out,
                           n_rows, 0, n_rows, err_row, err_ord);
    for (int32_t i = 0; i < n_threads; ++i) {
        if (st[i] < 0) {                      // lowest-row failure wins
            *err_row = erow[i];
            *err_ord = eord[i];
            return st[i];
        }
    }
    return base[n_threads];
}

// Striped row count: the sequential pre-count is otherwise the Amdahl
// bottleneck of the parallel ingest (two full-buffer scans, one serial).
int64_t csv_count_rows_mt(const char* buf, int64_t len, int32_t n_threads) {
    n_threads = stripe_count(len, n_threads);
    if (n_threads <= 1) return count_range(buf, buf + len);
    std::vector<const char*> bounds = stripe_bounds(buf, len, n_threads);
    std::vector<int64_t> rows(n_threads, 0);
    if (!run_threads(n_threads, [&](int32_t i) {
            rows[i] = count_range(bounds[i], bounds[i + 1]);
        }))
        return count_range(buf, buf + len);
    int64_t total = 0;
    for (int64_t r : rows) total += r;
    return total;
}

// Total bytes needed by csv_extract_column's output (tokens + '\n' each).
int64_t csv_column_bytes(const char* buf, int64_t len, char delim,
                         int32_t ordinal) {
    int64_t total = 0;
    for_each_token(buf, buf + len, delim, ordinal,
                   [&](const char* b, const char* e) { total += e - b + 1; });
    return total;
}

// Extract one column's tokens, '\n'-separated, into out (cap bytes).
// Returns bytes written, or -1 if cap is too small.
int64_t csv_extract_column(const char* buf, int64_t len, char delim,
                           int32_t ordinal, char* out, int64_t cap) {
    int64_t w = 0;
    bool fits = true;
    for_each_token(buf, buf + len, delim, ordinal,
                   [&](const char* b, const char* e) {
        int64_t n = e - b;
        if (!fits || w + n + 1 > cap) {
            fits = false;
            return;
        }
        memcpy(out + w, b, n);
        w += n;
        out[w++] = '\n';
    });
    return fits ? w : -1;
}

// The distinct tokens of one column, each followed by '\n' (a token holds
// none), in no order, as a malloc'd buffer in *out that the caller hands
// back to csv_free; *n_rows takes the rows visited. This is how a
// categorical with no declared vocabulary is discovered: every stripe
// keeps a set of its own over (ptr, len), the sets are merged, and what
// leaves is the column's few values, never a token per row. Returns the
// buffer's bytes, or -1 when it could not be allocated.
int64_t csv_distinct_column(const char* buf, int64_t len, char delim,
                            int32_t ordinal, int32_t n_threads, char** out,
                            int64_t* n_rows) {
    n_threads = stripe_count(len, n_threads);
    std::vector<Vocab> seen(n_threads);
    std::vector<int64_t> rows(n_threads, 0);
    auto scan = [&](int32_t i, const char* b, const char* e) {
        Vocab& mine = seen[i];
        rows[i] = for_each_token(b, e, delim, ordinal,
                                 [&mine](const char* tb, const char* te) {
            mine.add(tb, te - tb);
        });
    };
    if (n_threads > 1) {
        std::vector<const char*> bounds = stripe_bounds(buf, len, n_threads);
        if (!run_threads(n_threads, [&](int32_t i) {
                scan(i, bounds[i], bounds[i + 1]);
            }))
            n_threads = 1;                  // a failed spawn: start over
    }
    if (n_threads == 1) {
        seen.assign(1, Vocab());
        scan(0, buf, buf + len);
    }
    Vocab& all = seen[0];
    *n_rows = rows[0];
    for (int32_t i = 1; i < n_threads; ++i) {
        for (const std::string& v : seen[i].values) all.add(v.data(), v.size());
        *n_rows += rows[i];
    }
    int64_t size = 0;
    for (const std::string& v : all.values) size += v.size() + 1;
    char* w = static_cast<char*>(malloc(size > 0 ? size : 1));
    if (!w) return -1;
    *out = w;
    for (const std::string& v : all.values) {
        memcpy(w, v.data(), v.size());
        w += v.size();
        *w++ = '\n';
    }
    return size;
}

void csv_free(char* p) { free(p); }

// Ragged tokenize + dictionary-encode (the sequence-job ingest: markov /
// HMM lines are "id,class,s1,s2,..." with per-row token counts). One scan
// splits every non-empty line by `delim`, ASCII-trims each token, and
// encodes it against ONE vocabulary (n_vocab zero-terminated strings back
// to back in vocab_blob); unknown tokens (ids, free meta fields) encode
// as -1 and the CALLER decides which positions must be known. Outputs
// CSR: codes[total_tokens] + offsets[n_rows+1] (offsets[0] = 0).
// seq_token_count sizes the arrays; seq_encode returns rows written or
// -3 when the buffers are too small.
int64_t seq_token_count(const char* buf, int64_t len, char delim,
                        int64_t* out_tokens) {
    int64_t rows = 0, tokens = 0;
    const char* p = buf;
    const char* end = buf + len;
    while (p < end) {
        const char* nl = static_cast<const char*>(
            memchr(p, '\n', static_cast<size_t>(end - p)));
        const char* e = nl ? nl : end;
        // row-ness must match seq_encode EXACTLY: whitespace-only lines
        // are skipped even when the delimiter itself is a whitespace char
        bool all_ws = true;
        int64_t t = 1;
        for (const char* q = p; q < e; ++q) {
            if (*q == delim) ++t;
            if (*q != ' ' && *q != '\t' && *q != '\r') all_ws = false;
        }
        if (!all_ws) { ++rows; tokens += t; }
        p = nl ? nl + 1 : end;
    }
    *out_tokens = tokens;
    return rows;
}

int64_t seq_encode(const char* buf, int64_t len, char delim,
                   const char* vocab_blob, int32_t n_vocab,
                   int32_t* codes, int64_t max_tokens,
                   int64_t* offsets, int64_t max_rows) {
    Vocab vocab;
    const char* v = vocab_blob;
    for (int32_t i = 0; i < n_vocab; ++i) {
        size_t n = strlen(v);
        vocab.values.emplace_back(v, n);
        v += n + 1;
    }
    vocab.build();

    int64_t rows = 0, tok = 0;
    offsets[0] = 0;
    const char* p = buf;
    const char* end = buf + len;
    while (p < end) {
        const char* nl = static_cast<const char*>(
            memchr(p, '\n', static_cast<size_t>(end - p)));
        const char* e = nl ? nl : end;
        // whitespace-only lines don't produce rows (the Python line
        // reader's `if ln.strip()` filter); a delim-only line DOES (it
        // parses into empty tokens, exactly like the Python split path)
        bool all_ws = true;
        for (const char* s = p; s < e; ++s)
            if (*s != ' ' && *s != '\t' && *s != '\r') { all_ws = false; break; }
        if (all_ws) {
            p = nl ? nl + 1 : end;
            continue;
        }
        if (rows + 1 >= max_rows) return -3;   // offsets[++rows] must fit
        const char* ts = p;
        for (const char* s = p;; ++s) {
            if (s == e || *s == delim) {
                const char* a = ts;
                const char* b = s;
                while (a < b && (*a == ' ' || *a == '\t' || *a == '\r')) ++a;
                while (b > a && (b[-1] == ' ' || b[-1] == '\t'
                                 || b[-1] == '\r')) --b;
                if (tok >= max_tokens) return -3;
                codes[tok++] = vocab.find(a, static_cast<size_t>(b - a));
                ts = s + 1;
                if (s == e) break;
            }
        }
        offsets[++rows] = tok;
        p = nl ? nl + 1 : end;
    }
    return rows;
}

}  // extern "C"

// --------------------------------------------------------------------------
// The itemset miner's whole-file scan (models/association.py, the resident
// route): baskets "id,meta...,item,item,..." of unequal length, tokenised
// in stripes over lines, an item's token looked up through a flat table
// keyed on its bytes. Two entry points, each called once a job over the
// whole buffer: fia_scan finds the vocabulary and every item's basket
// count, fia_pack sets the baskets' bits in the resident bit columns.
// Row-ness and token identity are seq_encode's: a line of space, tab and
// CR alone is no row; a token is trimmed of those three; an empty token
// and the infrequent-item marker are no items.
// --------------------------------------------------------------------------
namespace {

// Open addressing over (key, len): a token of up to 8 bytes IS its key
// (zero-padded), so a hit compares two integers and touches no string; a
// longer token's key is its FNV-1a hash and a hit compares the bytes in
// the arena. A slot is 16 bytes: a thousand items probe inside the L1.
struct TokenTable {
    struct Slot { uint64_t key; uint32_t len; int32_t code; };
    std::vector<Slot> slots;
    std::vector<char> arena;          // the tokens, back to back, by code
    std::vector<uint64_t> offs;       // offs[code]: the token's first byte
    std::vector<uint32_t> lens;
    int shift = 64;

    static uint64_t key_of(const char* b, size_t n, const char* buf_end) {
        if (n > 8) return Vocab::hash(b, n);
        uint64_t k = 0;
        if (b + 8 <= buf_end) {
            memcpy(&k, b, 8);
            if (n < 8) k &= (1ull << (8 * n)) - 1;
        } else {
            memcpy(&k, b, n);
        }
        return k;
    }

    size_t home(uint64_t key, uint32_t n) const {
        return static_cast<size_t>(
            ((key ^ (n * 0xff51afd7ed558ccdull)) * 0x9e3779b97f4a7c15ull)
            >> shift);
    }

    void grow() {
        size_t cap = slots.empty() ? 64 : slots.size() * 2;
        std::vector<Slot> old;
        old.swap(slots);
        slots.assign(cap, Slot{0, 0, -1});
        shift = 64 - __builtin_ctzll(cap);
        for (const Slot& s : old) {
            if (s.code < 0) continue;
            size_t h = home(s.key, s.len);
            while (slots[h].code >= 0) h = (h + 1) & (cap - 1);
            slots[h] = s;
        }
    }

    int32_t size() const { return static_cast<int32_t>(lens.size()); }

    // The token's code, or -1; with `add`, a token not seen yet takes the
    // next code (only then are its bytes copied).
    template <bool add>
    int32_t code_of(const char* b, size_t n, const char* buf_end) {
        if (slots.empty()) {
            if (!add) return -1;
            grow();
        }
        const uint64_t key = key_of(b, n, buf_end);
        const size_t m = slots.size() - 1;
        size_t h = home(key, static_cast<uint32_t>(n));
        for (;; h = (h + 1) & m) {
            const Slot& s = slots[h];
            if (s.code < 0) break;
            if (s.key == key && s.len == n
                && (n <= 8 || memcmp(arena.data() + offs[s.code], b, n) == 0))
                return s.code;
        }
        if (!add) return -1;
        int32_t code = size();
        offs.push_back(arena.size());
        lens.push_back(static_cast<uint32_t>(n));
        arena.insert(arena.end(), b, b + n);
        slots[h] = Slot{key, static_cast<uint32_t>(n), code};
        if (static_cast<size_t>(code + 1) * 2 > slots.size()) grow();
        return code;
    }
};

// on_item(b, n) on every item token of every row of [p, end) and
// on_row() after each row's items, in file order: the fields from `skip`
// on, trimmed, the empty token and the marker left out. Returns the rows.
template <typename OnItem, typename OnRow>
int64_t for_each_basket(const char* p, const char* end, char delim,
                        int32_t skip, const char* marker, int64_t marker_len,
                        OnItem on_item, OnRow on_row) {
    int64_t rows = 0;
    while (p < end) {
        const char* nl = static_cast<const char*>(
            memchr(p, '\n', static_cast<size_t>(end - p)));
        const char* e = nl ? nl : end;
        const char* s = p;
        while (s < e && (*s == ' ' || *s == '\t' || *s == '\r')) ++s;
        if (s < e) {                              // not whitespace alone
            int32_t f = 0;
            const char* ts = p;
            for (s = p;; ++s) {
                if (s == e || *s == delim) {
                    if (f >= skip) {
                        const char* a = ts;
                        const char* b = s;
                        trim(a, b);
                        int64_t n = b - a;
                        if (n > 0 && !(n == marker_len
                                       && memcmp(a, marker, n) == 0))
                            on_item(a, static_cast<size_t>(n));
                    }
                    ++f;
                    ts = s + 1;
                    if (s == e) break;
                }
            }
            on_row();
            ++rows;
        }
        p = nl ? nl + 1 : end;
    }
    return rows;
}

}  // namespace

extern "C" {

// One file read whole into buf[0, len), striped over byte ranges: every
// stripe preads its own range, so the copy out of the page cache and the
// first touch of the buffer's pages are the stripes' work and not one
// thread's. Returns len, or -1 where the file could not be opened or is
// shorter than len.
int64_t file_read_mt(const char* path, char* buf, int64_t len,
                     int32_t n_threads) {
    int fd = open(path, O_RDONLY);
    if (fd < 0) return -1;
    n_threads = stripe_count(len, n_threads);
    std::vector<char> ok(n_threads, 1);
    auto read_range = [&](int32_t i) {
        int64_t at = len * i / n_threads;
        const int64_t stop = len * (i + 1) / n_threads;
        while (at < stop) {
            ssize_t got = pread(fd, buf + at, static_cast<size_t>(stop - at),
                                static_cast<off_t>(at));
            if (got <= 0) { ok[i] = 0; return; }
            at += got;
        }
    };
    if (n_threads == 1 || !run_threads(n_threads, read_range))
        for (int32_t i = 0; i < n_threads; ++i) { ok[i] = 1; read_range(i); }
    close(fd);
    for (char good : ok)
        if (!good) return -1;
    return len;
}

// Pass 1 of the resident route. Every stripe keeps a table of its own
// and counts, for each of its items, the baskets that hold it (an item
// twice in a basket counts once: the last basket that counted it is
// remembered). The stripes' tables are merged in file order, so an
// item's code is its rank by first appearance in the file whatever the
// thread count. *out takes one malloc'd buffer the caller hands back to
// csv_free: n_items int64 counts, then the tokens each followed by '\n'
// (*token_bytes of them). Returns the number of items, or -1 when the
// buffer could not be allocated.
int64_t fia_scan(const char* buf, int64_t len, char delim, int32_t skip,
                 const char* marker, int64_t marker_len, int32_t n_threads,
                 char** out, int64_t* token_bytes, int64_t* n_rows,
                 int64_t* n_tokens, int32_t* threads_used) {
    // a stripe's state is its thread's alone while it scans: it is built
    // on the thread's stack and moved here at the end, so that no two
    // threads write one cache line a token
    struct Stripe {
        TokenTable table;
        std::vector<int64_t> count;
        int64_t rows = 0, tokens = 0;
    };
    const char* end = buf + len;
    auto scan = [&](Stripe& out, const char* b, const char* e) {
        Stripe st;
        std::vector<int64_t> last;
        int64_t row = 0, tokens = 0;
        st.rows = for_each_basket(
            b, e, delim, skip, marker, marker_len,
            [&](const char* tb, size_t n) {
                int32_t c = st.table.code_of<true>(tb, n, end);
                if (static_cast<size_t>(c) == st.count.size()) {
                    st.count.push_back(0);
                    last.push_back(-1);
                }
                if (last[c] != row) {
                    last[c] = row;
                    ++st.count[c];
                }
                ++tokens;
            },
            [&] { ++row; });
        st.tokens = tokens;
        out = std::move(st);
    };
    n_threads = stripe_count(len, n_threads);
    std::vector<Stripe> stripes(n_threads);
    if (n_threads > 1) {
        std::vector<const char*> bounds = stripe_bounds(buf, len, n_threads);
        if (!run_threads(n_threads, [&](int32_t i) {
                scan(stripes[i], bounds[i], bounds[i + 1]);
            }))
            n_threads = 1;                  // a failed spawn: start over
    }
    if (n_threads == 1) {
        stripes.assign(1, Stripe());
        scan(stripes[0], buf, end);
    }
    *threads_used = n_threads;
    TokenTable all;
    std::vector<int64_t> count;
    *n_rows = 0;
    *n_tokens = 0;
    for (const Stripe& st : stripes) {
        for (int32_t c = 0; c < st.table.size(); ++c) {
            const char* tb = st.table.arena.data() + st.table.offs[c];
            // the arena's end bounds the 8-byte load here
            int32_t g = all.code_of<true>(
                tb, st.table.lens[c],
                st.table.arena.data() + st.table.arena.size());
            if (static_cast<size_t>(g) == count.size()) count.push_back(0);
            count[g] += st.count[c];
        }
        *n_rows += st.rows;
        *n_tokens += st.tokens;
    }
    const int64_t v = all.size();
    *token_bytes = static_cast<int64_t>(all.arena.size()) + v;
    char* w = static_cast<char*>(
        malloc(static_cast<size_t>(8 * v + *token_bytes + 1)));
    if (!w) return -1;
    *out = w;
    memcpy(w, count.data(), static_cast<size_t>(8 * v));
    w += 8 * v;
    for (int64_t c = 0; c < v; ++c) {
        memcpy(w, all.arena.data() + all.offs[c], all.lens[c]);
        w += all.lens[c];
        *w++ = '\n';
    }
    return v;
}

// Pass 2 of the resident route: every basket's bits set in the packed
// columns. `vocab` holds n_vocab tokens each followed by '\n' (fia_scan's
// order), item_row[code] the item's row in the columns or -1 for an item
// that is not kept. `cols` is uint32 [n_slabs, v_rows, slab_words], all
// zero on entry: basket t is bit t % 32 of word (t / 32) % slab_words of
// its item's row in slab t / 32 / slab_words. A token twice in a basket
// sets one bit twice. The stripes are csv_parse_mt's: counted, prefix-
// summed into a first basket each, then packed in parallel; two stripes
// share at most the word their edge falls in, which they OR atomically.
// Returns the baskets packed, -1 when the file holds another number of
// rows than n_rows, more than the slabs have room for, or `vocab` fewer
// tokens than n_vocab.
int64_t fia_pack(const char* buf, int64_t len, char delim, int32_t skip,
                 const char* marker, int64_t marker_len,
                 const char* vocab, int64_t vocab_bytes, int32_t n_vocab,
                 const int32_t* item_row, uint32_t* cols, int64_t v_rows, int64_t slab_words,
                 int64_t n_slabs, int64_t n_rows, int32_t n_threads) {
    if (n_rows > n_slabs * slab_words * 32) return -1;
    const char* end = buf + len;
    TokenTable table;
    {
        const char* v = vocab;
        const char* v_end = vocab + vocab_bytes;
        for (int32_t i = 0; i < n_vocab; ++i) {
            const char* nl = static_cast<const char*>(
                memchr(v, '\n', static_cast<size_t>(v_end - v)));
            if (!nl) return -1;
            table.code_of<true>(v, static_cast<size_t>(nl - v), nl);
            v = nl + 1;
        }
    }
    n_threads = stripe_count(len, n_threads);
    std::vector<const char*> bounds = stripe_bounds(buf, len, n_threads);
    std::vector<int64_t> base(n_threads + 1, 0);
    {
        std::vector<int64_t> rows(n_threads, 0);
        auto count = [&](int32_t i) {
            rows[i] = count_range(bounds[i], bounds[i + 1]);
        };
        if (n_threads == 1 || !run_threads(n_threads, count))
            for (int32_t i = 0; i < n_threads; ++i) count(i);
        for (int32_t i = 0; i < n_threads; ++i) base[i + 1] = base[i] + rows[i];
    }
    if (base[n_threads] != n_rows) return -1;
    auto pack = [&](int32_t i) {
        if (base[i + 1] == base[i]) return;
        int64_t t = base[i];
        const int64_t w_first = t >> 5, w_last = (base[i + 1] - 1) >> 5;
        uint32_t* at = nullptr;       // the basket's word in item row 0
        uint32_t bit = 0;
        bool edge = false;
        auto place = [&] {
            const int64_t w = t >> 5;
            at = cols + (w / slab_words) * v_rows * slab_words
                 + w % slab_words;
            bit = 1u << (t & 31);
            edge = w == w_first || w == w_last;
        };
        place();
        for_each_basket(
            bounds[i], bounds[i + 1], delim, skip, marker, marker_len,
            [&](const char* tb, size_t n) {
                int32_t c = table.code_of<false>(tb, n, end);
                if (c < 0 || item_row[c] < 0) return;
                uint32_t* word = at + item_row[c] * slab_words;
                if (edge) __atomic_fetch_or(word, bit, __ATOMIC_RELAXED);
                else *word |= bit;
            },
            [&] { if (++t < base[i + 1]) place(); });
    };
    if (n_threads == 1 || !run_threads(n_threads, pack)) {
        // a failed spawn may have packed some stripes: setting a bit
        // again changes nothing, so the whole file is packed in turn
        for (int32_t i = 0; i < n_threads; ++i) pack(i);
    }
    return n_rows;
}

}  // extern "C"

// --------------------------------------------------------------------------
// The forest's bootstrap counts (models/tree.py, RandomForestBuilder): how
// often each tree's sample holds each row, where tree t's sample is the
// t-th `integers(0, n, n)` of one numpy `default_rng`. The values are the
// job's contract, so this walks numpy's own stream, PCG64, and maps it by
// numpy's own rule; what it does not keep is numpy's order of work. The
// stream is a 128-bit LCG (output XSL-RR of the state after the step), so
// any position is reached in O(log) steps; `integers` below 2^32 takes
// 32-bit values from it, the low half of a 64-bit output and then the
// high half, and maps each by Lemire's rule on its own: m = x * n, the
// draw is m >> 32, and x is thrown away when uint32(m) < (2^32 - n) mod n.
// So the k-th draw of the forest is the k-th kept value of the stream,
// whoever generates it.
// --------------------------------------------------------------------------
namespace {

typedef unsigned __int128 u128;

struct Pcg64 {
    u128 state, inc;

    static u128 mult() {
        return (static_cast<u128>(0x2360ED051FC65DA4ull) << 64)
               | 0x4385DF649FCCF645ull;
    }

    // the generator `steps` outputs further on
    void advance(uint64_t steps) {
        u128 acc_mult = 1, acc_plus = 0, cur_mult = mult(), cur_plus = inc;
        for (; steps; steps >>= 1) {
            if (steps & 1) {
                acc_mult *= cur_mult;
                acc_plus = acc_plus * cur_mult + cur_plus;
            }
            cur_plus *= cur_mult + 1;
            cur_mult *= cur_mult;
        }
        state = acc_mult * state + acc_plus;
    }

    uint64_t next() {
        state = state * mult() + inc;
        const uint64_t hi = static_cast<uint64_t>(state >> 64);
        const uint64_t x = hi ^ static_cast<uint64_t>(state);
        const unsigned rot = static_cast<unsigned>(hi >> 58);
        return (x >> rot) | (x << ((64 - rot) & 63));
    }
};

// The 32-bit values of 64-bit outputs [q0, q1) of g's stream in numpy's
// order, each mapped by Lemire's rule for [0, n): kept(draw) on every one
// that gives a draw, until it returns false. Returns the 32-bit values
// read, the last kept one included.
template <typename Kept>
int64_t walk_draws(Pcg64 g, uint64_t q0, uint64_t q1, uint64_t n,
                   uint32_t threshold, Kept kept) {
    g.advance(q0);
    int64_t read = 0;
    for (uint64_t q = q0; q < q1; ++q) {
        const uint64_t out = g.next();
        const uint64_t lo = (out & 0xFFFFFFFFull) * n;
        ++read;
        if (static_cast<uint32_t>(lo) >= threshold && !kept(lo >> 32))
            return read;
        const uint64_t hi = (out >> 32) * n;
        ++read;
        if (static_cast<uint32_t>(hi) >= threshold && !kept(hi >> 32))
            return read;
    }
    return read;
}

}  // namespace

extern "C" {

// ws[t, r] += how often the t-th `integers(0, n, n)` of the PCG64 stream
// (state, inc: the halves of numpy's `bit_generator.state`, no 32-bit
// value buffered) draws row r, for trees t < n_trees; ws is int32
// [n_trees, stride], zeroed by the caller, 1 <= n <= stride, n < 2^32.
// The stream is cut into stretches of 64-bit outputs and a stretch into
// stripes, a thread each. Pass 1: a stripe jumps to its outputs and
// counts the values it keeps; a prefix sum then gives every stripe the
// ordinal of its first kept value, hence its tree. Pass 2: the stripe
// generates again and adds 1 to ws[ordinal / n, draw], atomically, as
// two stripes may draw one row; a batch of draws is prefetched before it
// is added, since every add is a cache miss of its own. The first stretch
// is as long as n_trees * n draws need on average, so about every other
// call takes a second, short one for what is still missing (the tests run
// that path). Returns the 32-bit values thrown away before the last draw,
// which is what numpy's own walk throws away; *max_weight takes the
// largest count written and *threads_used the stripes of the first
// stretch.
int64_t pcg64_bootstrap_counts(uint64_t state_hi, uint64_t state_lo,
                               uint64_t inc_hi, uint64_t inc_lo, int64_t n,
                               int32_t n_trees, int64_t stride, int32_t* ws,
                               int32_t n_threads, int32_t* max_weight,
                               int32_t* threads_used) {
    const Pcg64 gen{(static_cast<u128>(state_hi) << 64) | state_lo,
                    (static_cast<u128>(inc_hi) << 64) | inc_lo};
    const uint64_t un = static_cast<uint64_t>(n);
    const uint32_t threshold =
        static_cast<uint32_t>(((1ull << 32) - un) % un);
    const int64_t wanted = static_cast<int64_t>(n_trees) * n;
    constexpr int kBatch = 64;        // draws prefetched before they are added
    uint64_t q_at = 0;                // 64-bit outputs of earlier stretches
    int64_t kept_at = 0;              // draws they gave
    int64_t read_to_last = 0;
    int32_t largest = 0;
    *threads_used = 0;
    while (kept_at < wanted) {
        const u128 need = static_cast<u128>(wanted - kept_at);
        const uint64_t values = static_cast<uint64_t>(
            need + need * threshold / ((1ull << 32) - threshold)
            + (q_at ? 64 : 0));
        const uint64_t q_n = (values + 1) / 2;
        // a stripe of under 2^16 outputs is not worth its thread, unless
        // the caller asked for that many
        int64_t stripes = n_threads > 0 ? n_threads : std::min<int64_t>(
            std::thread::hardware_concurrency(), q_n >> 16);
        stripes = std::max<int64_t>(1, std::min<int64_t>(stripes, q_n));
        if (!*threads_used) *threads_used = static_cast<int32_t>(stripes);
        auto first = [&](int64_t i) {
            return q_at + static_cast<uint64_t>(
                static_cast<u128>(q_n) * i / stripes);
        };
        auto in_turn_or_threads = [&](auto fn, std::vector<char>& done) {
            if (stripes == 1
                || !run_threads(static_cast<int32_t>(stripes), fn))
                for (int64_t i = 0; i < stripes; ++i)
                    if (!done[i]) fn(static_cast<int32_t>(i));
        };

        std::vector<int64_t> base(stripes + 1, kept_at);
        {
            std::vector<int64_t> kept(stripes, 0);
            std::vector<char> done(stripes, 0);
            in_turn_or_threads([&](int32_t i) {
                int64_t k = 0;
                walk_draws(gen, first(i), first(i + 1), un, threshold,
                           [&](uint64_t) { ++k; return true; });
                kept[i] = k;
                done[i] = 1;
            }, done);
            for (int64_t i = 0; i < stripes; ++i)
                base[i + 1] = base[i] + kept[i];
        }

        std::vector<int32_t> most(stripes, 0);
        std::vector<int64_t> read(stripes, 0);
        std::vector<char> done(stripes, 0);
        in_turn_or_threads([&](int32_t i) {
            int64_t todo = std::min(base[i + 1], wanted) - base[i];
            if (todo > 0) {
                int32_t* row = ws + base[i] / n * stride;
                int64_t left_in_tree = n - base[i] % n;
                int32_t* at[kBatch];
                int filled = 0;
                int32_t top = 0;
                auto add = [&] {
                    for (int k = 0; k < filled; ++k)
                        top = std::max(top, 1 + __atomic_fetch_add(
                            at[k], 1, __ATOMIC_RELAXED));
                    filled = 0;
                };
                read[i] = walk_draws(
                    gen, first(i), first(i + 1), un, threshold,
                    [&](uint64_t draw) {
                        at[filled] = row + draw;
                        __builtin_prefetch(at[filled], 1);
                        if (++filled == kBatch) add();
                        if (--left_in_tree == 0) {
                            row += stride;
                            left_in_tree = n;
                        }
                        return --todo > 0;
                    });
                add();
                most[i] = top;
            }
            done[i] = 1;
        }, done);

        for (int64_t i = 0; i < stripes; ++i) {
            largest = std::max(largest, most[i]);
            if (base[i] < wanted && base[i + 1] >= wanted)
                read_to_last = static_cast<int64_t>(2 * first(i)) + read[i];
        }
        q_at += q_n;
        kept_at = base[stripes];
    }
    *max_weight = largest;
    return read_to_last - wanted;
}

}  // extern "C"

extern "C" {

// The kNN kernels' input matrix, written once from the parser's columns:
// out is float32 [n_padded, width], row-major, width = n_num + the sum of
// bins. In row i < n, column j < n_num is num[j][i] / max(ranges[j],
// 1e-9) (the floor rounded as numpy rounds the Python float, division in
// float32 as numpy divides), and each categorical f's bins[f] columns are
// zero but for `scale` at cat[f][i]; rows n..n_padded are zeros. The rows
// are striped by stripe_count's rule on the bytes written, so each page
// of `out` is first touched by the stripe that fills it. Returns the
// stripes cut; or -1 where some categorical holds a code outside
// [0, bins[f]), *err_field and *err_row then naming the first such
// (field, row) in row order.
int64_t knn_index_matrix(const float* const* num, const float* ranges,
                         int32_t n_num, const int32_t* const* cat,
                         const int32_t* bins, int32_t n_cat, float scale,
                         int64_t n, int64_t n_padded, float* out,
                         int32_t n_threads, int32_t* err_field,
                         int64_t* err_row) {
    int64_t width = n_num;
    for (int32_t f = 0; f < n_cat; ++f) width += bins[f];
    std::vector<float> den(n_num);
    for (int32_t j = 0; j < n_num; ++j)
        den[j] = std::max(ranges[j], static_cast<float>(1e-9));
    const int32_t stripes = stripe_count(
        n_padded * width * static_cast<int64_t>(sizeof(float)), n_threads);
    // a stripe's first bad code: (row, field), row -1 where none
    std::vector<int64_t> bad_row(stripes, -1);
    std::vector<int32_t> bad_field(stripes, -1);
    auto fill = [&](int32_t s) {
        const int64_t lo = n_padded * s / stripes;
        const int64_t hi = n_padded * (s + 1) / stripes;
        const int64_t filled = std::min(hi, n);
        bad_row[s] = -1;
        for (int64_t i = lo; i < filled; ++i) {
            float* row = out + i * width;
            for (int32_t j = 0; j < n_num; ++j) row[j] = num[j][i] / den[j];
            float* hot = row + n_num;
            for (int32_t f = 0; f < n_cat; ++f) {
                std::memset(hot, 0, sizeof(float) * bins[f]);
                const int32_t code = cat[f][i];
                if (code >= 0 && code < bins[f]) {
                    hot[code] = scale;
                } else if (bad_row[s] < 0) {
                    bad_row[s] = i;
                    bad_field[s] = f;
                }
                hot += bins[f];
            }
        }
        if (hi > filled && hi > lo) {
            const int64_t from = std::max(lo, filled);
            std::memset(out + from * width, 0,
                        sizeof(float) * width * (hi - from));
        }
    };
    if (stripes == 1 || !run_threads(stripes, fill))
        for (int32_t s = 0; s < stripes; ++s) fill(s);
    for (int32_t s = 0; s < stripes; ++s) {
        if (bad_row[s] >= 0) {
            *err_field = bad_field[s];
            *err_row = bad_row[s];
            return -1;
        }
    }
    return stripes;
}

}  // extern "C"
