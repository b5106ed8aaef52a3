// Consensus lane-block row copier: the [B, Lq] uint16 block a consensus
// group ships to the device is written once, a memcpy per pair row out of
// the layer store's packed `weight << 3 | code` pool (core/layers.py
// LayerStore.gather_qpw). The caller hands a zeroed block, so lanes past a
// row's length stay 0. ctypes releases the interpreter lock for the call.
// rt_copy_byte_rows is its twin over bytes: the aligner's [B, max_len]
// sequence blocks (ops/nw.py TpuAligner._pack_blocks), a memcpy per pair,
// and the seeding arena's code rows (ops/overlap_seed.py _pack_arena).
// rt_compact_seed_rows is the way back: a minimizer arena's selected
// slots written once, at their place in the final seed table.

#include <algorithm>
#include <cstdint>
#include <cstring>

extern "C" {

// For row r copy min(length[r], lq) lanes from pool + src[r] to row
// dest[r] of `out` (row-major, lq lanes a row). A row of length 0 copies
// nothing. The caller has checked src/length against the pool and dest
// against the block.
void rt_copy_lane_rows(int64_t count, const uint16_t* pool,
                       const int64_t* src, const int64_t* length,
                       const int64_t* dest, int64_t lq, uint16_t* out) {
    for (int64_t r = 0; r < count; ++r) {
        const int64_t n = std::min(length[r], lq);
        if (n > 0)
            std::memcpy(out + dest[r] * lq, pool + src[r],
                        static_cast<size_t>(n) * sizeof(uint16_t));
    }
}

// `pool` holds the rows' bytes back to back: row r's length[r] bytes go to
// the head of row r of `out` (row-major, row_len bytes a row). The caller
// has checked the lengths against row_len and their sum against the pool.
void rt_copy_byte_rows(int64_t count, const uint8_t* pool,
                       const int64_t* length, int64_t row_len,
                       uint8_t* out) {
    for (int64_t r = 0; r < count; ++r) {
        std::memcpy(out + r * row_len, pool,
                    static_cast<size_t>(length[r]));
        pool += length[r];
    }
}

// Walk rows [0, rows) of a minimizer arena's [*, P] planes in row-major
// order and write every selected slot as (hash, row_id[r], row_off[r] +
// column, strand) behind the last one. An entry whose (id, pos) equals
// the entry written before it is dropped: a position picked from both
// sides of a slice seam sits beside its twin. row_sel[r] is the row's
// selected count (the kernel's fourth output): a row of 0 is skipped and
// a row's walk ends at its last selected slot. Returns the entries
// written, or -1 before writing entry `cap` (the planes and the counts
// disagree; the caller raises).
int64_t rt_compact_seed_rows(int64_t rows, int64_t P, const uint32_t* h,
                             const uint8_t* sel, const uint8_t* strand,
                             const int32_t* row_id, const int32_t* row_off,
                             const int32_t* row_sel, int64_t cap,
                             uint32_t* out_h, int32_t* out_id,
                             int32_t* out_pos, uint8_t* out_strand) {
    int64_t n = 0;
    for (int64_t r = 0; r < rows; ++r) {
        int64_t left = row_sel[r];
        const uint8_t* s = sel + r * P;
        const int64_t base = r * P;
        auto emit = [&](int64_t j) -> bool {
            --left;
            const int32_t pos = row_off[r] + static_cast<int32_t>(j);
            if (n > 0 && out_pos[n - 1] == pos && out_id[n - 1] == row_id[r])
                return true;
            if (n >= cap) return false;
            out_h[n] = h[base + j];
            out_id[n] = row_id[r];
            out_pos[n] = pos;
            out_strand[n] = strand[base + j];
            ++n;
            return true;
        };
        int64_t j = 0;
#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
        // eight mask bytes a load, one step a set byte (one slot in three)
        for (; j + 8 <= P && left > 0; j += 8) {
            uint64_t word;
            std::memcpy(&word, s + j, 8);
            while (word) {
                const int b = __builtin_ctzll(word) >> 3;
                if (!emit(j + b)) return -1;
                word &= ~(0xFFull << (8 * b));
            }
        }
#endif
        for (; j < P && left > 0; ++j)
            if (s[j] && !emit(j)) return -1;
    }
    return n;
}

}  // extern "C"
