// Minimal non-Python consumer of the Arrow C Data Interface — the proof
// that the engine's binding surface (interop/arrow.py export_to_c) is a
// real ABI a foreign runtime can consume zero-copy, the role JNI handle
// passing plays in the reference (CastStrings.java:50-51 wraps returned
// handles; SURVEY.md §1 L5→L4 ownership contract).
//
// Deliberately standalone: the ArrowSchema/ArrowArray structs are declared
// from the Arrow C Data Interface specification (a stable ABI designed to
// be consumed without linking any Arrow library), exactly how a JVM's
// org.apache.arrow.c.Data bridge or a Rust arrow-ffi consumer sees them.
// The consumer walks the exported struct-array-of-columns, reads values
// straight out of the shared buffers (no copies), and honors the release
// callbacks — the ownership handshake the spec requires.

#include <cstdint>
#include <cstring>

extern "C" {

// Arrow C Data Interface (verbatim from the spec)
struct ArrowSchema {
  const char* format;
  const char* name;
  const char* metadata;
  int64_t flags;
  int64_t n_children;
  struct ArrowSchema** children;
  struct ArrowSchema* dictionary;
  void (*release)(struct ArrowSchema*);
  void* private_data;
};

struct ArrowArray {
  int64_t length;
  int64_t null_count;
  int64_t offset;
  int64_t n_buffers;
  int64_t n_children;
  const void** buffers;
  struct ArrowArray** children;
  struct ArrowArray* dictionary;
  void (*release)(struct ArrowArray*);
  void* private_data;
};

static bool bit_is_set(uint8_t const* bits, int64_t i) {
  return bits == nullptr || ((bits[i >> 3] >> (i & 7)) & 1) != 0;
}

// Consume one exported table (a struct array of columns):
//   int_sum    = sum of every valid value of every int64 ("l") column
//   str_bytes  = total UTF-8 payload bytes of every utf8 ("u") column
//   list_sum   = sum of every element of every list<int64> ("+l") column
//   null_count = total top-level nulls across those columns
// Returns the row count, or -1 on contract violation. Calls release() on
// both structs (ownership passes to this consumer, per the spec).
int64_t arrow_consume(struct ArrowArray* arr, struct ArrowSchema* schema,
                      int64_t* int_sum, int64_t* str_bytes,
                      int64_t* list_sum, int64_t* null_count) {
  *int_sum = 0;
  *str_bytes = 0;
  *list_sum = 0;
  *null_count = 0;
  if (arr == nullptr || schema == nullptr) return -1;
  if (std::strcmp(schema->format, "+s") != 0) return -1;
  if (arr->n_children != schema->n_children) return -1;
  int64_t const rows = arr->length;

  for (int64_t c = 0; c < arr->n_children; c++) {
    struct ArrowArray const* col = arr->children[c];
    struct ArrowSchema const* cs = schema->children[c];
    char const* fmt = cs->format;
    uint8_t const* validity =
        static_cast<uint8_t const*>(col->n_buffers > 0 ? col->buffers[0]
                                                       : nullptr);
    int64_t const off = col->offset;
    if (std::strcmp(fmt, "l") == 0) {                 // int64
      if (col->n_buffers < 2) return -1;
      int64_t const* data = static_cast<int64_t const*>(col->buffers[1]);
      for (int64_t i = 0; i < col->length; i++) {
        if (bit_is_set(validity, off + i)) *int_sum += data[off + i];
        else (*null_count)++;
      }
    } else if (std::strcmp(fmt, "u") == 0) {          // utf8
      if (col->n_buffers < 3) return -1;
      int32_t const* offs = static_cast<int32_t const*>(col->buffers[1]);
      for (int64_t i = 0; i < col->length; i++) {
        if (bit_is_set(validity, off + i))
          *str_bytes += offs[off + i + 1] - offs[off + i];
        else (*null_count)++;
      }
    } else if (std::strcmp(fmt, "+l") == 0 && cs->n_children == 1 &&
               std::strcmp(cs->children[0]->format, "l") == 0) {
      if (col->n_buffers < 2 || col->n_children != 1) return -1;
      int32_t const* offs = static_cast<int32_t const*>(col->buffers[1]);
      struct ArrowArray const* child = col->children[0];
      if (child->n_buffers < 2) return -1;
      uint8_t const* cvalid =
          static_cast<uint8_t const*>(child->buffers[0]);
      int64_t const* cdata = static_cast<int64_t const*>(child->buffers[1]);
      for (int64_t i = 0; i < col->length; i++) {
        if (!bit_is_set(validity, off + i)) {
          (*null_count)++;
          continue;
        }
        for (int32_t j = offs[off + i]; j < offs[off + i + 1]; j++)
          if (bit_is_set(cvalid, child->offset + j))
            *list_sum += cdata[child->offset + j];
      }
    }
    // other formats: tolerated and skipped (forward compatibility)
  }

  // ownership handshake: the exporter handed these to us; release them
  if (schema->release != nullptr) schema->release(schema);
  if (arr->release != nullptr) arr->release(arr);
  return rows;
}

}  // extern "C"
