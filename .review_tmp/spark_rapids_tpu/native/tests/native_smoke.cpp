// Native test driver — the reference's gtest tier (SURVEY.md §4 tier 1: one
// native test executable per kernel family) plus its sanitizer tier in one:
// ci/sanitizer.sh compiles this WITH the library sources under
// -fsanitize=address,undefined and runs it directly, so every C++ path is
// memcheck'd without the LD_PRELOAD interceptor limitations of sanitizing
// through the Python interpreter.
//
// Covers: resource-adaptor state machine (block/wake, BUFN escalation via
// deadlock detection, injection, metrics drain) and the parquet reader
// (footer parse, PLAIN + dictionary decode, def levels) against a file
// written by the harness (ci/sanitizer.sh) with pyarrow.

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <string>
#include <thread>
#include <vector>

// ---- C ABI under test -------------------------------------------------------

extern "C" {
void* sra_create(char const* log_loc);
void sra_destroy(void* h);
char const* sra_last_error();
int sra_start_dedicated_task_thread(void* h, int64_t tid, int64_t task_id,
                                    int64_t self);
int sra_task_done(void* h, int64_t task_id, int64_t self);
int sra_pre_alloc(void* h, int64_t tid, int is_cpu, int blocking, int64_t self,
                  int* recursive);
int sra_post_alloc_success(void* h, int64_t tid, int is_cpu, int was_recursive,
                           int64_t self);
int sra_post_alloc_failed(void* h, int64_t tid, int is_cpu, int was_oom,
                          int blocking, int was_recursive, int64_t self,
                          int* retry);
int sra_dealloc(void* h, int64_t tid, int is_cpu, int64_t self);
int sra_check_and_break_deadlocks(void* h, int64_t self);
int sra_get_thread_state(void* h, int64_t tid);
int sra_force_retry_oom(void* h, int64_t tid, int num, int filter, int skip);
int64_t sra_get_and_reset_num_retry(void* h, int64_t task_id);

void* pqf_parse(uint8_t const* buf, int64_t len);
int64_t pqf_num_rows(void* h);
int pqf_filter_groups(void* h, int64_t part_offset, int64_t part_length);
int64_t pqf_serialize(void* h, uint8_t* out, int64_t cap);
void pqf_free(void* h);

void* pqr_open_ex(uint8_t const* buf, int64_t len, int32_t copy);
char const* pqr_last_error();
int64_t pqr_num_rows(void* h);
int32_t pqr_num_row_groups(void* h);
int32_t pqr_num_leaves(void* h);
int32_t pqr_leaf_kind(void* h, int32_t i);
int32_t pqr_leaf_ancestry(void* h, int32_t i, int32_t* max_def,
                          int32_t* max_rep, int32_t* desc, int32_t cap);
int32_t pqr_read_nested_column(void* h, int32_t rg, int32_t leaf,
                               uint8_t* values, int64_t* values_nbytes,
                               int32_t* lengths, uint8_t* def_levels,
                               uint8_t* rep_levels, int64_t* num_slots,
                               int64_t* num_present);
int64_t pqr_row_group_num_rows(void* h, int32_t rg);
int32_t pqr_read_list_column(void* h, int32_t rg, int32_t leaf,
                             uint8_t* values, int64_t* values_nbytes,
                             int32_t* lengths, uint8_t* elem_defined,
                             int64_t* num_elem_slots, int64_t* num_present,
                             int32_t* row_counts, uint8_t* row_valid,
                             int64_t* num_rows);
int32_t pqr_read_def_levels(void* h, int32_t rg, int32_t leaf, uint8_t* out);
int32_t pqr_read_column(void* h, int32_t rg, int32_t leaf, uint8_t* values,
                        int64_t* values_nbytes, int32_t* lengths,
                        uint8_t* defined, int64_t* num_present);
void pqr_free(void* h);
}

// status codes mirrored from resource_adaptor.cpp (SRA_*)
enum { OK = 0, RETRY_OOM = 1 };
// thread states, numerically identical to RmmSparkThreadState.java
enum { ST_RUNNING = 0, ST_BLOCKED = 3 };

static int g_failures = 0;

#define CHECK(cond)                                                     \
  do {                                                                  \
    if (!(cond)) {                                                      \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__,      \
                   #cond);                                              \
      g_failures++;                                                     \
    }                                                                   \
  } while (0)

// ---- resource adaptor scenarios ---------------------------------------------

static void test_alloc_retry_block_wake() {
  void* h = sra_create("");
  CHECK(h != nullptr);
  // thread 1 (task 1) allocates fine
  CHECK(sra_start_dedicated_task_thread(h, 1, 1, 1) == OK);
  int rec = 0;
  CHECK(sra_pre_alloc(h, 1, 0, 1, 1, &rec) == OK);
  CHECK(sra_post_alloc_success(h, 1, 0, rec, 1) == OK);

  // thread 2 (task 2, lower priority) fails its alloc and blocks; thread
  // 1's dealloc wakes it
  CHECK(sra_start_dedicated_task_thread(h, 2, 2, 2) == OK);
  std::atomic<int> t2_phase{0};
  std::thread t2([&] {
    int rec2 = 0;
    CHECK(sra_pre_alloc(h, 2, 0, 1, 2, &rec2) == OK);
    int retry = 0;
    CHECK(sra_post_alloc_failed(h, 2, 0, 1, 1, rec2, 2, &retry) == OK);
    CHECK(retry == 1);
    t2_phase = 1;
    // blocked now; this pre_alloc waits until thread 1 deallocs
    int rc = sra_pre_alloc(h, 2, 0, 1, 2, &rec2);
    t2_phase = 2;
    if (rc == OK) {
      CHECK(sra_post_alloc_success(h, 2, 0, rec2, 2) == OK);
    } else {
      CHECK(rc == RETRY_OOM);  // deadlock watchdog may fire first
    }
  });
  while (t2_phase.load() < 1) std::this_thread::yield();
  for (int i = 0; i < 100 && sra_get_thread_state(h, 2) != ST_BLOCKED; i++)
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
  CHECK(sra_get_thread_state(h, 2) == ST_BLOCKED);
  CHECK(sra_dealloc(h, 1, 0, 1) == OK);  // wakes thread 2
  t2.join();
  CHECK(sra_task_done(h, 1, 1) == OK);
  CHECK(sra_task_done(h, 2, 2) == OK);
  sra_destroy(h);
}

static void test_deadlock_escalates_to_retry_oom() {
  void* h = sra_create("");
  CHECK(sra_start_dedicated_task_thread(h, 7, 7, 7) == OK);
  int rec = 0, retry = 0;
  CHECK(sra_pre_alloc(h, 7, 0, 1, 7, &rec) == OK);
  CHECK(sra_post_alloc_failed(h, 7, 0, 1, 1, rec, 7, &retry) == OK);
  // the only task is blocked -> deadlock -> lowest priority gets BUFN_THROW
  std::thread blocked([&] {
    int r2 = 0;
    int rc = sra_pre_alloc(h, 7, 0, 1, 7, &r2);
    CHECK(rc == RETRY_OOM);
  });
  // keep firing the watchdog until the worker escapes: on a loaded machine
  // the first check may run before the worker reaches BLOCKED, and a single
  // missed check would leave it blocked forever (join would hang CI)
  std::atomic<bool> done{false};
  std::thread joiner([&] { blocked.join(); done = true; });
  for (int i = 0; i < 10000 && !done.load(); i++) {
    CHECK(sra_check_and_break_deadlocks(h, 99) == OK);
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
  }
  CHECK(done.load());
  joiner.join();
  CHECK(sra_get_and_reset_num_retry(h, 7) >= 1);
  CHECK(sra_task_done(h, 7, 7) == OK);
  sra_destroy(h);
}

static void test_injection() {
  void* h = sra_create("");
  CHECK(sra_start_dedicated_task_thread(h, 3, 3, 3) == OK);
  CHECK(sra_force_retry_oom(h, 3, 1, 0, 0) == OK);
  int rec = 0;
  CHECK(sra_pre_alloc(h, 3, 0, 1, 3, &rec) == RETRY_OOM);
  CHECK(sra_pre_alloc(h, 3, 0, 1, 3, &rec) == OK);  // one-shot
  CHECK(sra_post_alloc_success(h, 3, 0, rec, 3) == OK);
  CHECK(sra_dealloc(h, 3, 0, 3) == OK);
  CHECK(sra_task_done(h, 3, 3) == OK);
  sra_destroy(h);
}

// ---- parquet reader ---------------------------------------------------------

static void test_parquet(char const* path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    std::fprintf(stderr, "SKIP parquet test: cannot open %s\n", path);
    return;
  }
  std::vector<uint8_t> bytes((std::istreambuf_iterator<char>(f)),
                             std::istreambuf_iterator<char>());
  void* h = pqr_open_ex(bytes.data(), int64_t(bytes.size()), 0);
  if (!h) std::fprintf(stderr, "pqr_open: %s\n", pqr_last_error());
  CHECK(h != nullptr);
  if (!h) return;
  CHECK(pqr_num_rows(h) == 1000);
  CHECK(pqr_num_leaves(h) >= 2);
  for (int32_t rg = 0; rg < pqr_num_row_groups(h); rg++) {
    for (int32_t leaf = 0; leaf < pqr_num_leaves(h); leaf++) {
      int64_t nbytes = 0, present = 0;
      CHECK(pqr_read_column(h, rg, leaf, nullptr, &nbytes, nullptr, nullptr,
                            &present) == 0);
      std::vector<uint8_t> values(size_t(nbytes) + 1);
      std::vector<int32_t> lengths(size_t(present) + 1);
      std::vector<uint8_t> defined(4096);
      CHECK(pqr_read_column(h, rg, leaf, values.data(), &nbytes,
                            lengths.data(), defined.data(), &present) == 0);
      CHECK(present <= 1000);
    }
  }
  // column 0 ("x" int64, written as iota): spot-check values
  int64_t nbytes = 0, present = 0;
  CHECK(pqr_read_column(h, 0, 0, nullptr, &nbytes, nullptr, nullptr,
                        &present) == 0);
  std::vector<uint8_t> values(static_cast<size_t>(nbytes));
  std::vector<uint8_t> defined(4096);
  CHECK(pqr_read_column(h, 0, 0, values.data(), &nbytes, nullptr,
                        defined.data(), &present) == 0);
  int64_t v0, v9;
  std::memcpy(&v0, values.data(), 8);
  std::memcpy(&v9, values.data() + 9 * 8, 8);
  CHECK(v0 == 0 && v9 == 9);
  pqr_free(h);

  // footer parse / filter / re-serialize path (parquet_footer.cpp)
  uint32_t flen;
  std::memcpy(&flen, bytes.data() + bytes.size() - 8, 4);
  CHECK(flen + 12ull <= bytes.size());
  void* fh = pqf_parse(bytes.data() + bytes.size() - 8 - flen, flen);
  CHECK(fh != nullptr);
  if (fh) {
    CHECK(pqf_num_rows(fh) == 1000);
    CHECK(pqf_filter_groups(fh, 0, int64_t(bytes.size())) == 0);
    int64_t need = pqf_serialize(fh, nullptr, 0);
    CHECK(need > 0);
    std::vector<uint8_t> out(static_cast<size_t>(need));
    CHECK(pqf_serialize(fh, out.data(), need) == need);
    pqf_free(fh);
  }
}

// nested file: list + struct + delta-encoded columns (written by the
// sanitizer driver) — exercises level decode, Dremel reassembly and the
// delta decoders under ASan
static void test_parquet_nested(char const* path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    std::fprintf(stderr, "SKIP nested parquet test: cannot open %s\n", path);
    return;
  }
  std::vector<uint8_t> bytes((std::istreambuf_iterator<char>(f)),
                             std::istreambuf_iterator<char>());
  void* h = pqr_open_ex(bytes.data(), int64_t(bytes.size()), 0);
  CHECK(h != nullptr);
  if (!h) { std::fprintf(stderr, "%s\n", pqr_last_error()); return; }
  bool saw_list = false, saw_struct = false, saw_nested = false;
  for (int32_t leaf = 0; leaf < pqr_num_leaves(h); leaf++) {
    int32_t kind = pqr_leaf_kind(h, leaf);
    for (int32_t rg = 0; rg < pqr_num_row_groups(h); rg++) {
      size_t const rg_rows = size_t(pqr_row_group_num_rows(h, rg));
      if (kind == 4) {
        // generalized nesting (MAP / LIST<STRUCT> / STRUCT<LIST>): raw
        // level streams + ancestry descriptor round-trip under ASan
        saw_nested = true;
        int32_t max_def = 0, max_rep = 0;
        int32_t desc[64];
        int32_t n_ints = pqr_leaf_ancestry(h, leaf, &max_def, &max_rep,
                                           desc, 64);
        CHECK(n_ints > 0 && n_ints % 4 == 0);
        CHECK(max_rep >= 1 && max_def >= max_rep);
        int64_t nbytes = 0, slots = 0, present = 0;
        CHECK(pqr_read_nested_column(h, rg, leaf, nullptr, &nbytes, nullptr,
                                     nullptr, nullptr, &slots,
                                     &present) == 0);
        std::vector<uint8_t> values(size_t(nbytes) + 1);
        std::vector<int32_t> lengths(size_t(present) + 1);
        std::vector<uint8_t> defs(size_t(slots) + 1);
        std::vector<uint8_t> reps(size_t(slots) + 1);
        CHECK(pqr_read_nested_column(h, rg, leaf, values.data(), &nbytes,
                                     lengths.data(), defs.data(),
                                     reps.data(), &slots, &present) == 0);
        int64_t rows = 0, got_present = 0;
        for (int64_t i = 0; i < slots; i++) {
          CHECK(defs[size_t(i)] <= max_def && reps[size_t(i)] <= max_rep);
          if (reps[size_t(i)] == 0) rows++;
          if (defs[size_t(i)] == max_def) got_present++;
        }
        CHECK(rows == int64_t(rg_rows));
        CHECK(got_present == present);
      } else if (kind == 1) {
        saw_list = true;
        int64_t nbytes = 0, slots = 0, present = 0, rows = 0;
        CHECK(pqr_read_list_column(h, rg, leaf, nullptr, &nbytes, nullptr,
                                   nullptr, &slots, &present, nullptr,
                                   nullptr, &rows) == 0);
        std::vector<uint8_t> values(size_t(nbytes) + 1);
        std::vector<int32_t> lengths(size_t(present) + 1);
        std::vector<uint8_t> edef(size_t(slots) + 1);
        std::vector<int32_t> counts(size_t(rows) + 1);
        std::vector<uint8_t> valid(size_t(rows) + 1);
        CHECK(pqr_read_list_column(h, rg, leaf, values.data(), &nbytes,
                                   lengths.data(), edef.data(), &slots,
                                   &present, counts.data(), valid.data(),
                                   &rows) == 0);
        int64_t total = 0;
        for (int64_t i = 0; i < rows; i++) total += counts[size_t(i)];
        CHECK(total == slots);
      } else if (kind == 0 || kind == 2) {
        if (kind == 2) saw_struct = true;
        int64_t nbytes = 0, present = 0;
        CHECK(pqr_read_column(h, rg, leaf, nullptr, &nbytes, nullptr,
                              nullptr, &present) == 0);
        std::vector<uint8_t> defs(rg_rows + 1);
        if (kind == 2)
          CHECK(pqr_read_def_levels(h, rg, leaf, defs.data()) == 0);
        std::vector<uint8_t> values(size_t(nbytes) + 1);
        std::vector<int32_t> lengths(size_t(present) + 1);
        std::vector<uint8_t> defined(rg_rows + 1);
        CHECK(pqr_read_column(h, rg, leaf, values.data(), &nbytes,
                              lengths.data(), defined.data(), &present) == 0);
      }
    }
  }
  // the ci/sanitizer.sh fixture always carries kind-4 fields (mp/ls/sl):
  // a schema-classification regression must fail loudly, not skip coverage
  CHECK(saw_list && saw_struct && saw_nested);
  pqr_free(h);
}

// parse every truncation/corruption of a real file: must error or succeed,
// never crash or over-read (the ASan build turns over-reads into failures)
static void test_parquet_truncation_fuzz(char const* path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    std::fprintf(stderr, "SKIP parquet fuzz test: cannot open %s\n", path);
    return;
  }
  std::vector<uint8_t> bytes((std::istreambuf_iterator<char>(f)),
                             std::istreambuf_iterator<char>());
  auto poke = [](void* h) {
    // size every column through its kind's entry point — nested (kind 4)
    // decode paths walk the raw level streams and must stay in-bounds on
    // corrupt input too
    int64_t nbytes = 0, present = 0, slots = 0;
    for (int32_t leaf = 0; leaf < pqr_num_leaves(h) && leaf < 8; leaf++) {
      if (pqr_leaf_kind(h, leaf) == 4)
        pqr_read_nested_column(h, 0, leaf, nullptr, &nbytes, nullptr,
                               nullptr, nullptr, &slots, &present);
      else
        pqr_read_column(h, 0, leaf, nullptr, &nbytes, nullptr, nullptr,
                        &present);
    }
  };
  for (size_t cut = 0; cut < bytes.size(); cut += 97) {
    void* h = pqr_open_ex(bytes.data(), int64_t(cut), 1);
    if (h) {
      poke(h);
      pqr_free(h);
    }
  }
  // single-byte corruptions of the footer region
  size_t const foot = bytes.size() > 512 ? bytes.size() - 512 : 0;
  for (size_t i = foot; i < bytes.size(); i += 13) {
    std::vector<uint8_t> mut = bytes;
    mut[i] ^= 0x5A;
    void* h = pqr_open_ex(mut.data(), int64_t(mut.size()), 1);
    if (h) {
      poke(h);
      pqr_free(h);
    }
  }
  std::printf("parquet truncation/corruption fuzz OK\n");
}

int main(int argc, char** argv) {
  test_alloc_retry_block_wake();
  test_deadlock_escalates_to_retry_oom();
  test_injection();
  if (argc > 1) test_parquet(argv[1]);
  if (argc > 2) test_parquet_nested(argv[2]);
  if (argc > 2) test_parquet_truncation_fuzz(argv[2]);
  if (g_failures) {
    std::fprintf(stderr, "%d native test failures\n", g_failures);
    return 1;
  }
  std::printf("native smoke OK\n");
  return 0;
}
