// Consensus lane-block row copier: the [B, Lq] uint16 block a consensus
// group ships to the device is written once, a memcpy per pair row out of
// the layer store's packed `weight << 3 | code` pool (core/layers.py
// LayerStore.gather_qpw). The caller hands a zeroed block, so lanes past a
// row's length stay 0. ctypes releases the interpreter lock for the call.
// rt_copy_byte_rows is its twin over bytes: the aligner's [B, max_len]
// sequence blocks (ops/nw.py TpuAligner._pack_blocks), a memcpy per pair.

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

}  // extern "C"
