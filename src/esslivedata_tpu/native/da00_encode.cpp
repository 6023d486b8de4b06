// Native da00 serializer: the publish hot path.
//
// The dashboard-facing publish serializes ~10 outputs x ~4 variables per
// pulse; the Python flatbuffers Builder costs ~140us per variable, which
// made da00 encoding the single largest CPU cost of the ingest->publish
// latency path (~8ms of a ~15ms step at LOKI scale, round-4 profile).
// This is a minimal prepend-style flatbuffers writer specialized to the
// da00 layout pinned by schemas/da00_dataarray.fbs and the golden wire
// tests. It mirrors the Python builder's operation order and vtable
// deduplication so its output is byte-identical to kafka/wire.py's
// encode_da00 (asserted by tests/kafka/native_da00_test.py).
//
// Build: g++ -O3 -shared -fPIC -std=c++17 da00_encode.cpp -o _da00.so
// (driven by native/__init__.py, same pattern as ingest.cpp).

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

struct Builder {
    uint8_t* buf;      // caller-provided scratch, filled from the END
    int64_t cap;
    int64_t head;      // index of first used byte (grows downward)
    int64_t minalign;
    bool overflow;
    // Offsets (from end) of previously written vtables for dedup.
    std::vector<int64_t> vtables;

    explicit Builder(uint8_t* out, int64_t out_cap)
        : buf(out), cap(out_cap), head(out_cap), minalign(1),
          overflow(false) {}

    int64_t offset() const { return cap - head; }

    void make_space(int64_t n) {
        if (head - n < 0) overflow = true;
    }

    void place_u8(uint8_t v) {
        make_space(1);
        if (overflow) return;
        buf[--head] = v;
    }

    void place_bytes(const uint8_t* p, int64_t n) {
        make_space(n);
        if (overflow) return;
        head -= n;
        std::memcpy(buf + head, p, n);
    }

    template <typename T>
    void place(T v) {
        make_space(sizeof(T));
        if (overflow) return;
        head -= sizeof(T);
        std::memcpy(buf + head, &v, sizeof(T));
    }

    // Pad so that after writing `additional` bytes the next write of
    // `size` bytes is aligned (python Builder.Prep).
    void prep(int64_t size, int64_t additional) {
        if (size > minalign) minalign = size;
        int64_t align_size =
            ((~(offset() + additional)) + 1) & (size - 1);
        make_space(align_size);
        if (overflow) return;
        for (int64_t i = 0; i < align_size; ++i) buf[--head] = 0;
    }

    template <typename T>
    void prepend(T v) {
        prep(sizeof(T), 0);
        place(v);
    }

    void prepend_uoffset(int64_t off) {
        prep(4, 0);
        place<uint32_t>(static_cast<uint32_t>(offset() - off + 4));
    }

    int64_t create_string(const uint8_t* s, int64_t n) {
        prep(4, n + 1);
        place_u8(0);
        place_bytes(s, n);
        place<uint32_t>(static_cast<uint32_t>(n));
        return offset();
    }

    void start_vector(int64_t elem_size, int64_t count, int64_t align) {
        prep(4, elem_size * count);
        prep(align, elem_size * count);
    }

    int64_t end_vector(int64_t count) {
        place<uint32_t>(static_cast<uint32_t>(count));
        return offset();
    }

};

// Table assembly state (python Builder.StartObject/EndObject).
struct TableWriter {
    Builder* b;
    int64_t slots[16];
    int n_slots;
    int64_t object_start;  // b->offset() at StartObject

    TableWriter(Builder* builder, int n) : b(builder), n_slots(n) {
        for (int i = 0; i < n; ++i) slots[i] = 0;
        object_start = b->offset();
    }

    void slot_uoffset(int i, int64_t off) {
        b->prepend_uoffset(off);
        slots[i] = b->offset();
    }

    void slot_i64(int i, int64_t v, int64_t def) {
        if (v == def) return;
        b->prepend<int64_t>(v);
        slots[i] = b->offset();
    }

    void slot_i8(int i, int8_t v, int8_t def) {
        if (v == def) return;
        b->prepend<int8_t>(v);
        slots[i] = b->offset();
    }

    int64_t end() {
        // soffset placeholder.
        b->prep(4, 0);
        b->place<int32_t>(0);
        int64_t object_offset = b->offset();
        // Trim trailing unused slots (python WriteVtable trims).
        int n = n_slots;
        while (n > 0 && slots[n - 1] == 0) --n;
        // Candidate vtable content.
        uint16_t vt[2 + 16];
        int64_t vt_len = (2 + n) * 2;
        vt[0] = static_cast<uint16_t>(vt_len);
        vt[1] = static_cast<uint16_t>(object_offset - object_start);
        for (int i = 0; i < n; ++i)
            vt[2 + i] = slots[i]
                            ? static_cast<uint16_t>(object_offset - slots[i])
                            : 0;
        // Dedup against previously written vtables (python
        // Builder.WriteVtable's VtableEqual scan).
        for (int64_t existing : b->vtables) {
            const uint8_t* evt = b->buf + (b->cap - existing);
            uint16_t elen;
            std::memcpy(&elen, evt, 2);
            if (elen != vt_len) continue;
            if (std::memcmp(evt, vt, vt_len) == 0) {
                // Reuse: point the soffset at the existing vtable.
                int32_t so = static_cast<int32_t>(existing - object_offset);
                std::memcpy(b->buf + (b->cap - object_offset), &so, 4);
                return object_offset;
            }
        }
        // Write a fresh vtable (fields prepended in reverse).
        for (int i = n - 1; i >= 0; --i)
            b->prepend<uint16_t>(vt[2 + i]);
        b->prepend<uint16_t>(vt[1]);
        b->prepend<uint16_t>(vt[0]);
        int64_t vtable_offset = b->offset();
        int32_t so = static_cast<int32_t>(vtable_offset - object_offset);
        if (!b->overflow)
            std::memcpy(b->buf + (b->cap - object_offset), &so, 4);
        b->vtables.push_back(vtable_offset);
        return object_offset;
    }
};

}  // namespace

extern "C" {

// Returns bytes written (from the FRONT of out), or -1 on overflow
// (caller retries with a bigger buffer), or -2 on invalid input.
//
// String table: strings_blob with n_strs+1 offsets; indices reference
// it. label_idx/source_idx entries of -1 omit the slot. Variable i's
// data is data_lens[i] bytes at data_ptrs[i], read where the caller
// holds it: no concatenation first. The message is built from the END
// of out: the return value n is its length, and it is out[out_cap - n,
// out_cap) (no move to the front).
int64_t ld_da00_encode(
    const uint8_t* strings_blob, const int64_t* str_offs, int32_t n_strs,
    int32_t source_name_idx, int64_t timestamp, int32_t n_vars,
    const int32_t* name_idx, const int32_t* unit_idx,
    const int32_t* label_idx, const int32_t* source_idx,
    const int8_t* dtype_codes,
    const int32_t* axes_start, const int32_t* axes_count,
    const int32_t* axes_idx_flat,
    const int32_t* dims_start, const int32_t* dims_count,
    const int64_t* shapes_flat,
    const uint8_t* const* data_ptrs, const int64_t* data_lens,
    uint8_t* out, int64_t out_cap) {
    if (n_vars < 0 || source_name_idx < 0 || source_name_idx >= n_strs)
        return -2;
    Builder b(out, out_cap);

    auto str_ptr = [&](int32_t idx) {
        return strings_blob + str_offs[idx];
    };
    auto str_len = [&](int32_t idx) {
        return str_offs[idx + 1] - str_offs[idx];
    };

    std::vector<int64_t> var_offs(static_cast<size_t>(n_vars));
    for (int32_t i = 0; i < n_vars; ++i) {
        // Mirror _encode_da00_variable's write order exactly.
        int64_t data_len = data_lens[i];
        int64_t data_off;
        if (data_len == 0) {
            b.start_vector(1, 0, 1);
            data_off = b.end_vector(0);
        } else {
            b.prep(4, data_len);
            b.prep(1, data_len);
            b.place_bytes(data_ptrs[i], data_len);
            data_off = b.end_vector(data_len);
        }
        int64_t shape_off = 0;
        int32_t nd = dims_count[i];
        if (nd > 0) {
            b.start_vector(8, nd, 8);
            const int64_t* dims = shapes_flat + dims_start[i];
            for (int32_t d = nd - 1; d >= 0; --d) b.place<int64_t>(dims[d]);
            shape_off = b.end_vector(nd);
        }
        int64_t axes_off = 0;
        int32_t na = axes_count[i];
        if (na > 0) {
            // Python creates the axis strings in order, then the vector.
            int64_t axis_offs[16];
            if (na > 16) return -2;
            for (int32_t a = 0; a < na; ++a) {
                int32_t idx = axes_idx_flat[axes_start[i] + a];
                axis_offs[a] = b.create_string(str_ptr(idx), str_len(idx));
            }
            b.start_vector(4, na, 4);
            for (int32_t a = na - 1; a >= 0; --a)
                b.prepend_uoffset(axis_offs[a]);
            axes_off = b.end_vector(na);
        }
        int64_t source_off = 0;
        if (source_idx[i] >= 0)
            source_off =
                b.create_string(str_ptr(source_idx[i]), str_len(source_idx[i]));
        int64_t label_off = 0;
        if (label_idx[i] >= 0)
            label_off =
                b.create_string(str_ptr(label_idx[i]), str_len(label_idx[i]));
        int64_t unit_off =
            b.create_string(str_ptr(unit_idx[i]), str_len(unit_idx[i]));
        int64_t name_off =
            b.create_string(str_ptr(name_idx[i]), str_len(name_idx[i]));

        TableWriter t(&b, 8);
        t.slot_uoffset(0, name_off);
        t.slot_uoffset(1, unit_off);
        if (label_off) t.slot_uoffset(2, label_off);
        if (source_off) t.slot_uoffset(3, source_off);
        t.slot_i8(4, dtype_codes[i], 0);
        if (axes_off) t.slot_uoffset(5, axes_off);
        if (shape_off) t.slot_uoffset(6, shape_off);
        t.slot_uoffset(7, data_off);
        var_offs[static_cast<size_t>(i)] = t.end();
        if (b.overflow) return -1;
    }

    b.start_vector(4, n_vars, 4);
    for (int32_t i = n_vars - 1; i >= 0; --i)
        b.prepend_uoffset(var_offs[static_cast<size_t>(i)]);
    int64_t vars_vec = b.end_vector(n_vars);
    int64_t src_off = b.create_string(str_ptr(source_name_idx),
                                      str_len(source_name_idx));

    TableWriter root(&b, 3);
    root.slot_uoffset(0, src_off);
    root.slot_i64(1, timestamp, 0);
    root.slot_uoffset(2, vars_vec);
    int64_t root_off = root.end();

    // Finish(root, file_identifier=b"da00"): python does
    // Prep(minalign, uoffset+file_id), places the id, then the root.
    b.prep(b.minalign, 4 + 4);
    static const uint8_t fid[4] = {'d', 'a', '0', '0'};
    b.place_bytes(fid, 4);
    b.prepend_uoffset(root_off);
    if (b.overflow) return -1;

    return b.cap - b.head;
}

}  // extern "C"
