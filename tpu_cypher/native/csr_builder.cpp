// Native host-side hot paths: CSR topology build and SNAP edge-list parsing.
//
// The reference delegates its host-side heavy lifting to the JVM engines
// (Spark/Flink DataFrame machinery); our TPU runtime's host tier does the
// equivalent work here in C++ — the compute path stays JAX/XLA, but graph
// ingest (text -> edges) and topology compaction (edges -> CSR) are
// bandwidth-bound host loops where interpreter overhead dominates:
//
//  * parse_edge_list: single-pass scan of a SNAP-style buffer ('#' comments,
//    whitespace/comma separated int pairs) — replaces the per-line Python
//    loop in io/edge_list.py.
//  * build_csr: map raw int64 element ids to compact int32 indices (binary
//    search over the sorted unique id vector) and produce a CSR lexsorted by
//    (src, dst) via two stable counting sorts, O(E + N) — replaces
//    np.searchsorted + np.lexsort (O(E log E)) in CsrGraph.build.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in the image).
// Build: g++ -O3 -march=native -shared -fPIC csr_builder.cpp -o _native.so

#include <cstdint>
#include <cstring>
#include <vector>
#include <algorithm>

extern "C" {

// Parse whitespace/comma-separated "src dst" pairs; skip '#...' comment and
// blank lines. Returns number of edges, or -(byte offset + 1) on malformed
// input. out_src/out_dst must have room for one edge per input line.
int64_t parse_edge_list(const char* buf, int64_t len,
                        int64_t* out_src, int64_t* out_dst) {
    int64_t count = 0;
    int64_t i = 0;
    while (i < len) {
        // skip leading spaces/commas
        while (i < len && (buf[i] == ' ' || buf[i] == '\t' || buf[i] == ',' ||
                           buf[i] == '\r')) i++;
        if (i >= len) break;
        if (buf[i] == '\n') { i++; continue; }
        if (buf[i] == '#') {            // comment line
            while (i < len && buf[i] != '\n') i++;
            continue;
        }
        // parse two integers; each must be followed by a separator/EOL so
        // "2.5" or "2x" is rejected exactly like the Python loader's int()
        int64_t vals[2];
        for (int k = 0; k < 2; k++) {
            while (i < len && (buf[i] == ' ' || buf[i] == '\t' || buf[i] == ','))
                i++;
            bool neg = false;
            if (i < len && (buf[i] == '-' || buf[i] == '+')) {
                neg = buf[i] == '-';
                i++;
            }
            if (i >= len || buf[i] < '0' || buf[i] > '9') return -(i + 1);
            int64_t v = 0;
            while (i < len && buf[i] >= '0' && buf[i] <= '9') {
                v = v * 10 + (buf[i] - '0');
                i++;
            }
            if (i < len && buf[i] != ' ' && buf[i] != '\t' && buf[i] != ',' &&
                buf[i] != '\r' && buf[i] != '\n')
                return -(i + 1);
            vals[k] = neg ? -v : v;
        }
        out_src[count] = vals[0];
        out_dst[count] = vals[1];
        count++;
        // skip to end of line (ignore trailing columns, e.g. weights)
        while (i < len && buf[i] != '\n') i++;
    }
    return count;
}

// Deduplicate + sort node ids in place semantics: input ids (n_in), output
// into out_ids; returns unique count. out_ids must have room for n_in.
int64_t unique_sorted(const int64_t* ids, int64_t n_in, int64_t* out_ids) {
    std::vector<int64_t> v(ids, ids + n_in);
    std::sort(v.begin(), v.end());
    auto end = std::unique(v.begin(), v.end());
    int64_t n = end - v.begin();
    std::memcpy(out_ids, v.data(), n * sizeof(int64_t));
    return n;
}

// Build CSR from compact-mapped edges.
//   node_ids: sorted unique int64 ids (n of them)
//   src/dst:  raw int64 endpoint ids (e of them); every id MUST be present
//             in node_ids (returns -1 otherwise)
//   row_ptr:  out, n+1 int32
//   col_idx:  out, e int32 (dst compact ids, lexsorted by (src, dst))
//   src_idx:  out, e int32 (src compact id per edge, sorted)
// Two stable counting sorts give the (src, dst) lexsort in O(E + N).
int32_t build_csr(const int64_t* node_ids, int64_t n,
                  const int64_t* src, const int64_t* dst, int64_t e,
                  int32_t* row_ptr, int32_t* col_idx, int32_t* src_idx) {
    // compact-map endpoints via binary search
    std::vector<int32_t> s(e), d(e);
    const int64_t* begin = node_ids;
    const int64_t* end = node_ids + n;
    for (int64_t i = 0; i < e; i++) {
        const int64_t* ps = std::lower_bound(begin, end, src[i]);
        const int64_t* pd = std::lower_bound(begin, end, dst[i]);
        if (ps == end || *ps != src[i] || pd == end || *pd != dst[i]) return -1;
        s[i] = (int32_t)(ps - begin);
        d[i] = (int32_t)(pd - begin);
    }
    // counting sort by dst (stable)
    std::vector<int64_t> cnt(n + 1, 0);
    std::vector<int32_t> s1(e), d1(e);
    for (int64_t i = 0; i < e; i++) cnt[d[i] + 1]++;
    for (int64_t i = 0; i < n; i++) cnt[i + 1] += cnt[i];
    {
        std::vector<int64_t> pos(cnt.begin(), cnt.end());
        for (int64_t i = 0; i < e; i++) {
            int64_t p = pos[d[i]]++;
            s1[p] = s[i];
            d1[p] = d[i];
        }
    }
    // stable counting sort by src -> final lexsort (src, dst)
    std::fill(cnt.begin(), cnt.end(), 0);
    for (int64_t i = 0; i < e; i++) cnt[s1[i] + 1]++;
    for (int64_t i = 0; i < n; i++) cnt[i + 1] += cnt[i];
    for (int64_t i = 0; i <= n; i++) row_ptr[i] = (int32_t)cnt[i];
    {
        std::vector<int64_t> pos(cnt.begin(), cnt.end());
        for (int64_t i = 0; i < e; i++) {
            int64_t p = pos[s1[i]]++;
            col_idx[p] = d1[i];
            src_idx[p] = s1[i];
        }
    }
    return 0;
}

}  // extern "C"
