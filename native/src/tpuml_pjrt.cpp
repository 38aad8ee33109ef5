// libtpuml PJRT client — the native layer's accelerator path.
//
// This is the TPU-native answer to the reference's CUDA entry points
// (/root/reference/native/src/rapidsml_jni.cu:172-336): where the reference's
// native dgemm/dgemm_b call cuBLAS on device buffers it cudaMalloc'd per
// call, this module speaks the XLA **PJRT C API** (SURVEY.md §7 step 2):
// dlopen a PJRT plugin (libtpu or any other implementation), create
// a client once, compile StableHLO modules for the Gram and transform
// matmuls, keep the executables cached per shape, and run them on TPU HBM —
// no per-call handle churn, no CUDA toolkit, no Python in the loop.
//
// Everything is plain C ABI (ctypes-bound like tpuml.cpp) and the plugin is
// loaded at RUNTIME, so libtpuml.so itself links against nothing but libdl.
// Version note: structs carry struct_size (PJRT's append-only ABI), so a
// client built against a newer header drives older plugins (probed OK:
// header v0.90 against a v0.54 plugin).

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <mutex>
#include <string>
#include <vector>

#include "../third_party/pjrt_c_api.h"

#define TPUML_API extern "C" __attribute__((visibility("default")))

namespace {

std::mutex g_mu;
const PJRT_Api* g_api = nullptr;
PJRT_Client* g_client = nullptr;
std::vector<PJRT_Device*> g_devices;  // addressable
std::string g_last_error;
std::vector<PJRT_LoadedExecutable*> g_executables;
std::map<std::string, int> g_kernel_cache;  // shape-keyed convenience kernels

// CompileOptionsProto{executable_build_options{num_replicas:1,num_partitions:1}}
const unsigned char kMinimalCompileOptions[] = {0x1a, 0x04, 0x20, 0x01, 0x28, 0x01};

void set_error(const std::string& what, PJRT_Error* err) {
  g_last_error = what;
  if (err && g_api) {
    PJRT_Error_Message_Args m;
    std::memset(&m, 0, sizeof m);
    m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
    m.error = err;
    g_api->PJRT_Error_Message(&m);
    g_last_error += ": ";
    g_last_error.append(m.message, m.message_size);
    PJRT_Error_Destroy_Args d;
    std::memset(&d, 0, sizeof d);
    d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
    d.error = err;
    g_api->PJRT_Error_Destroy(&d);
  }
}

// 0 on success; records the error otherwise.
int fail_if(PJRT_Error* err, const char* what) {
  if (!err) return 0;
  set_error(what, err);
  return -1;
}

// For advisory queries whose failure is tolerated: destroys the error
// object (the caller owns it per the PJRT protocol) without touching
// g_last_error. Returns true when the call succeeded.
bool query_ok(PJRT_Error* err) {
  if (!err) return true;
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = err;
  g_api->PJRT_Error_Destroy(&d);
  return false;
}

int await_and_destroy(PJRT_Event* ev, const char* what) {
  if (!ev) return 0;
  PJRT_Event_Await_Args a;
  std::memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  a.event = ev;
  int rc = fail_if(g_api->PJRT_Event_Await(&a), what);
  PJRT_Event_Destroy_Args d;
  std::memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  g_api->PJRT_Event_Destroy(&d);
  return rc;
}

int compile_locked(const char* mlir, const void* copts, size_t copts_len) {
  PJRT_Program prog;
  std::memset(&prog, 0, sizeof prog);
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char*>(mlir);
  prog.code_size = std::strlen(mlir);
  prog.format = "mlir";
  prog.format_size = 4;

  PJRT_Client_Compile_Args cc;
  std::memset(&cc, 0, sizeof cc);
  cc.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  cc.client = g_client;
  cc.program = &prog;
  cc.compile_options =
      static_cast<const char*>(copts ? copts : (const void*)kMinimalCompileOptions);
  cc.compile_options_size = copts ? copts_len : sizeof kMinimalCompileOptions;
  if (fail_if(g_api->PJRT_Client_Compile(&cc), "compile")) return -1;
  g_executables.push_back(cc.executable);
  return static_cast<int>(g_executables.size()) - 1;
}

int execute_locked(int handle, const float* const* inputs,
                   const int64_t* const* dims, const int* ndims, int n_inputs,
                   float* out, size_t out_bytes) {
  if (handle < 0 || handle >= static_cast<int>(g_executables.size())) {
    g_last_error = "bad executable handle";
    return -1;
  }
  std::vector<PJRT_Buffer*> in_bufs(n_inputs, nullptr);
  int rc = 0;
  for (int i = 0; i < n_inputs && !rc; i++) {
    PJRT_Client_BufferFromHostBuffer_Args bh;
    std::memset(&bh, 0, sizeof bh);
    bh.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bh.client = g_client;
    bh.data = inputs[i];
    bh.type = PJRT_Buffer_Type_F32;
    bh.dims = dims[i];
    bh.num_dims = ndims[i];
    bh.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    bh.device = g_devices[0];
    rc = fail_if(g_api->PJRT_Client_BufferFromHostBuffer(&bh), "h2d");
    if (!rc) {
      in_bufs[i] = bh.buffer;  // record BEFORE await so a failure still frees
      rc = await_and_destroy(bh.done_with_host_buffer, "h2d-await");
    }
  }
  if (rc) {
    for (PJRT_Buffer* b : in_bufs) {
      if (!b) continue;
      PJRT_Buffer_Destroy_Args bd;
      std::memset(&bd, 0, sizeof bd);
      bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      bd.buffer = b;
      g_api->PJRT_Buffer_Destroy(&bd);
    }
    return rc;
  }

  PJRT_ExecuteOptions eo;
  std::memset(&eo, 0, sizeof eo);
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  PJRT_Buffer* const* arg_lists[1] = {in_bufs.data()};
  PJRT_Buffer* out_list[1] = {nullptr};
  PJRT_Buffer** out_lists[1] = {out_list};
  PJRT_Event* done[1] = {nullptr};

  PJRT_LoadedExecutable_Execute_Args ex;
  std::memset(&ex, 0, sizeof ex);
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = g_executables[handle];
  ex.options = &eo;
  ex.num_devices = 1;
  ex.num_args = n_inputs;
  ex.argument_lists = arg_lists;
  ex.output_lists = out_lists;
  ex.device_complete_events = done;
  rc = fail_if(g_api->PJRT_LoadedExecutable_Execute(&ex), "execute");
  if (!rc) rc = await_and_destroy(done[0], "execute-await");

  if (!rc) {
    // Ask the plugin to deliver the output in dense row-major directly: an
    // explicit untiled descending minor_to_major host_layout makes the
    // plugin do any detiling/transpose during the copy, so no host-side
    // fixup is needed regardless of the device layout.
    PJRT_Buffer_Dimensions_Args bd;
    std::memset(&bd, 0, sizeof bd);
    bd.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    bd.buffer = out_list[0];
    size_t out_rank = 0;
    bool have_dims = query_ok(g_api->PJRT_Buffer_Dimensions(&bd));
    if (have_dims) out_rank = bd.num_dims;
    int64_t row_major_m2m[8];
    for (size_t i = 0; i < out_rank && i < 8; i++)
      row_major_m2m[i] = static_cast<int64_t>(out_rank - 1 - i);
    PJRT_Buffer_MemoryLayout host_layout;
    std::memset(&host_layout, 0, sizeof host_layout);
    host_layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
    host_layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
    host_layout.tiled.minor_to_major = row_major_m2m;
    host_layout.tiled.minor_to_major_size = out_rank;
    host_layout.tiled.num_tiles = 0;

    PJRT_Buffer_ToHostBuffer_Args th;
    std::memset(&th, 0, sizeof th);
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = out_list[0];
    th.dst = out;
    th.dst_size = out_bytes;
    bool explicit_layout = out_rank > 0 && out_rank <= 8;
    bool layout_rejected = false;
    if (explicit_layout) th.host_layout = &host_layout;
    rc = fail_if(g_api->PJRT_Buffer_ToHostBuffer(&th), "d2h");
    if (rc && explicit_layout) {
      // Plugin rejected the explicit layout request; retry source-layout
      // copy and normalize on the host below.
      explicit_layout = false;
      layout_rejected = true;
      std::memset(&th, 0, sizeof th);
      th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      th.src = out_list[0];
      th.dst = out;
      th.dst_size = out_bytes;
      rc = fail_if(g_api->PJRT_Buffer_ToHostBuffer(&th), "d2h");
    }
    if (!rc) rc = await_and_destroy(th.event, "d2h-await");
    // When the copy used the SOURCE buffer's layout (no/rejected explicit
    // layout), normalize to row-major on the host: transpose a dense
    // column-major 2-D output; fail loudly on a genuinely tiled layout —
    // the bytes are tile-swizzled and a naive transpose would scramble
    // them further, so returning them silently is worse than an error.
    if (!rc && !explicit_layout) {
      PJRT_Buffer_GetMemoryLayout_Args gl;
      std::memset(&gl, 0, sizeof gl);
      gl.struct_size = PJRT_Buffer_GetMemoryLayout_Args_STRUCT_SIZE;
      gl.buffer = out_list[0];
      if (query_ok(g_api->PJRT_Buffer_GetMemoryLayout(&gl)) &&
          gl.layout.type == PJRT_Buffer_MemoryLayout_Type_Tiled) {
        if (gl.layout.tiled.num_tiles != 0) {
          set_error(std::string("d2h: output buffer has a tiled device "
                    "layout that was copied as-is (") +
                    (layout_rejected
                         ? "the plugin rejected an explicit row-major "
                           "host layout"
                         : "no explicit host layout was requested: "
                           "dimensions query failed or rank > 8") +
                    "); refusing to return tile-swizzled bytes", nullptr);
          rc = -1;
        } else if (have_dims && bd.num_dims == 2 &&
                   gl.layout.tiled.minor_to_major_size == 2 &&
                   gl.layout.tiled.minor_to_major[0] == 0) {
          int64_t r = bd.dims[0], c = bd.dims[1];
          std::vector<float> tmp(out, out + static_cast<size_t>(r) * c);
          for (int64_t i = 0; i < r; i++)
            for (int64_t j = 0; j < c; j++)
              out[i * c + j] = tmp[j * r + i];
        }
      }
    }
  }

  for (PJRT_Buffer* b : in_bufs) {
    if (!b) continue;
    PJRT_Buffer_Destroy_Args bd;
    std::memset(&bd, 0, sizeof bd);
    bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    bd.buffer = b;
    g_api->PJRT_Buffer_Destroy(&bd);
  }
  if (out_list[0]) {
    PJRT_Buffer_Destroy_Args bd;
    std::memset(&bd, 0, sizeof bd);
    bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    bd.buffer = out_list[0];
    g_api->PJRT_Buffer_Destroy(&bd);
  }
  return rc;
}

// MLIR for C = AᵀB (trans=true: A r×m, B r×n → m×n; the Gram/covariance and
// reference-dgemm_b shape) or C = A·B (trans=false: A m×k, B k×n → m×n; the
// transform shape). HIGHEST precision: this is the parity path.
std::string dot_mlir(bool trans_a, int64_t d0, int64_t d1, int64_t d2) {
  char buf[640];
  if (trans_a) {
    std::snprintf(
        buf, sizeof buf,
        "module {\n"
        "  func.func @main(%%arg0: tensor<%ldx%ldxf32>, %%arg1: tensor<%ldx%ldxf32>) -> tensor<%ldx%ldxf32> {\n"
        "    %%0 = stablehlo.dot_general %%arg0, %%arg1, contracting_dims = [0] x [0], precision = [HIGHEST, HIGHEST] : (tensor<%ldx%ldxf32>, tensor<%ldx%ldxf32>) -> tensor<%ldx%ldxf32>\n"
        "    return %%0 : tensor<%ldx%ldxf32>\n  }\n}\n",
        (long)d0, (long)d1, (long)d0, (long)d2, (long)d1, (long)d2, (long)d0,
        (long)d1, (long)d0, (long)d2, (long)d1, (long)d2, (long)d1, (long)d2);
  } else {
    std::snprintf(
        buf, sizeof buf,
        "module {\n"
        "  func.func @main(%%arg0: tensor<%ldx%ldxf32>, %%arg1: tensor<%ldx%ldxf32>) -> tensor<%ldx%ldxf32> {\n"
        "    %%0 = stablehlo.dot_general %%arg0, %%arg1, contracting_dims = [1] x [0], precision = [HIGHEST, HIGHEST] : (tensor<%ldx%ldxf32>, tensor<%ldx%ldxf32>) -> tensor<%ldx%ldxf32>\n"
        "    return %%0 : tensor<%ldx%ldxf32>\n  }\n}\n",
        (long)d0, (long)d1, (long)d1, (long)d2, (long)d0, (long)d2, (long)d0,
        (long)d1, (long)d1, (long)d2, (long)d0, (long)d2, (long)d0, (long)d2);
  }
  return std::string(buf);
}

int cached_dot(bool trans_a, int64_t d0, int64_t d1, int64_t d2) {
  char key[64];
  std::snprintf(key, sizeof key, "%c:%ld:%ld:%ld", trans_a ? 't' : 'n',
                (long)d0, (long)d1, (long)d2);
  auto it = g_kernel_cache.find(key);
  if (it != g_kernel_cache.end()) return it->second;
  int h = compile_locked(dot_mlir(trans_a, d0, d1, d2).c_str(), nullptr, 0);
  if (h >= 0) g_kernel_cache[key] = h;
  return h;
}

// Single-operand G = XᵀX: one H2D transfer of X instead of two (the Gram is
// the dominant input of the covariance path).
int cached_gram(int64_t rows, int64_t n) {
  char key[64];
  std::snprintf(key, sizeof key, "g:%ld:%ld", (long)rows, (long)n);
  auto it = g_kernel_cache.find(key);
  if (it != g_kernel_cache.end()) return it->second;
  char buf[640];
  std::snprintf(
      buf, sizeof buf,
      "module {\n"
      "  func.func @main(%%arg0: tensor<%ldx%ldxf32>) -> tensor<%ldx%ldxf32> {\n"
      "    %%0 = stablehlo.dot_general %%arg0, %%arg0, contracting_dims = [0] x [0], precision = [HIGHEST, HIGHEST] : (tensor<%ldx%ldxf32>, tensor<%ldx%ldxf32>) -> tensor<%ldx%ldxf32>\n"
      "    return %%0 : tensor<%ldx%ldxf32>\n  }\n}\n",
      (long)rows, (long)n, (long)n, (long)n, (long)rows, (long)n, (long)rows,
      (long)n, (long)n, (long)n, (long)n, (long)n);
  int h = compile_locked(buf, nullptr, 0);
  if (h >= 0) g_kernel_cache[key] = h;
  return h;
}

}  // namespace

TPUML_API int tpuml_pjrt_available() { return 1; }

TPUML_API const char* tpuml_pjrt_last_error() {
  std::lock_guard<std::mutex> lk(g_mu);
  return g_last_error.c_str();
}

TPUML_API int tpuml_pjrt_api_version(int* major, int* minor) {
  std::lock_guard<std::mutex> lk(g_mu);
  if (!g_api) return -1;
  *major = g_api->pjrt_api_version.major_version;
  *minor = g_api->pjrt_api_version.minor_version;
  return 0;
}

// Create the process-wide client. Options: kinds[i] 0 = string (svals[i]),
// 1 = int64 (ivals[i]). Idempotent — a second init returns 0 without
// touching the existing client (mirrors the reference loader's singleton,
// JniRAPIDSML.java:34-58).
TPUML_API int tpuml_pjrt_init(const char* plugin_path,
                              const char* const* names, const int* kinds,
                              const char* const* svals, const int64_t* ivals,
                              int n_options) {
  std::lock_guard<std::mutex> lk(g_mu);
  if (g_client) return 0;
  void* h = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!h) {
    g_last_error = std::string("dlopen ") + plugin_path + ": " + dlerror();
    return -1;
  }
  auto get_api =
      reinterpret_cast<const PJRT_Api* (*)()>(dlsym(h, "GetPjrtApi"));
  if (!get_api) {
    g_last_error = std::string("GetPjrtApi missing in ") + plugin_path;
    return -1;
  }
  g_api = get_api();

  PJRT_Plugin_Initialize_Args pi;
  std::memset(&pi, 0, sizeof pi);
  pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (fail_if(g_api->PJRT_Plugin_Initialize(&pi), "plugin-init")) return -1;

  std::vector<PJRT_NamedValue> opts(n_options);
  for (int i = 0; i < n_options; i++) {
    std::memset(&opts[i], 0, sizeof(PJRT_NamedValue));
    opts[i].struct_size = PJRT_NamedValue_STRUCT_SIZE;
    opts[i].name = names[i];
    opts[i].name_size = std::strlen(names[i]);
    if (kinds[i] == 0) {
      opts[i].type = PJRT_NamedValue_kString;
      opts[i].string_value = svals[i];
      opts[i].value_size = std::strlen(svals[i]);
    } else {
      opts[i].type = PJRT_NamedValue_kInt64;
      opts[i].int64_value = ivals[i];
    }
  }

  PJRT_Client_Create_Args c;
  std::memset(&c, 0, sizeof c);
  c.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  c.create_options = opts.data();
  c.num_options = n_options;
  if (fail_if(g_api->PJRT_Client_Create(&c), "client-create")) return -1;
  g_client = c.client;

  PJRT_Client_AddressableDevices_Args ad;
  std::memset(&ad, 0, sizeof ad);
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = g_client;
  int rc = fail_if(g_api->PJRT_Client_AddressableDevices(&ad), "devices");
  if (!rc) {
    g_devices.assign(ad.addressable_devices,
                     ad.addressable_devices + ad.num_addressable_devices);
    if (g_devices.empty()) {
      g_last_error = "no addressable devices";
      rc = -1;
    }
  }
  if (rc) {
    // Tear the half-built client down so a retry re-runs creation instead
    // of "succeeding" against an empty device list.
    PJRT_Client_Destroy_Args cd;
    std::memset(&cd, 0, sizeof cd);
    cd.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    cd.client = g_client;
    g_api->PJRT_Client_Destroy(&cd);
    g_client = nullptr;
    g_devices.clear();
    return -1;
  }
  return 0;
}

TPUML_API int tpuml_pjrt_device_count() {
  std::lock_guard<std::mutex> lk(g_mu);
  return g_client ? static_cast<int>(g_devices.size()) : -1;
}

// Compile an arbitrary MLIR module; returns an executable handle (>= 0).
// copts = serialized xla CompileOptionsProto (NULL ⇒ minimal 1-replica).
TPUML_API int tpuml_pjrt_compile(const char* mlir, const void* copts,
                                 size_t copts_len) {
  std::lock_guard<std::mutex> lk(g_mu);
  if (!g_client) {
    g_last_error = "pjrt client not initialized";
    return -1;
  }
  return compile_locked(mlir, copts, copts_len);
}

// Run a compiled module: n f32 inputs, one f32 output.
TPUML_API int tpuml_pjrt_execute_f32(int handle, const float* const* inputs,
                                     const int64_t* const* dims,
                                     const int* ndims, int n_inputs,
                                     float* out, size_t out_bytes) {
  std::lock_guard<std::mutex> lk(g_mu);
  if (!g_client) {
    g_last_error = "pjrt client not initialized";
    return -1;
  }
  return execute_locked(handle, inputs, dims, ndims, n_inputs, out, out_bytes);
}

// Gram G = XᵀX on the accelerator — the reference's per-partition dgemm
// (rapidsml_jni.cu:172-258) with the covariance call shape
// (RapidsRowMatrix.scala:195-196). X is rows×n row-major; out n×n.
TPUML_API int tpuml_pjrt_gram_f32(const float* x, int64_t rows, int64_t n,
                                  float* out) {
  std::lock_guard<std::mutex> lk(g_mu);
  if (!g_client) {
    g_last_error = "pjrt client not initialized";
    return -1;
  }
  int h = cached_gram(rows, n);
  if (h < 0) return -1;
  const float* inputs[1] = {x};
  const int64_t d[2] = {rows, n};
  const int64_t* dims[1] = {d};
  const int nd[1] = {2};
  return execute_locked(h, inputs, dims, nd, 1, out,
                        static_cast<size_t>(n) * n * sizeof(float));
}

// C = AᵀB — the reference's dgemm_b transform entry (rapidsml_jni.cu:260-336,
// OP_T/OP_N, alpha=1, beta=0), sans its device-buffer leak.
TPUML_API int tpuml_pjrt_dot_tn_f32(const float* a, const float* b,
                                    int64_t rows, int64_t m, int64_t n,
                                    float* out) {
  std::lock_guard<std::mutex> lk(g_mu);
  if (!g_client) {
    g_last_error = "pjrt client not initialized";
    return -1;
  }
  int h = cached_dot(true, rows, m, n);
  if (h < 0) return -1;
  const float* inputs[2] = {a, b};
  const int64_t da[2] = {rows, m}, db[2] = {rows, n};
  const int64_t* dims[2] = {da, db};
  const int nd[2] = {2, 2};
  return execute_locked(h, inputs, dims, nd, 2, out,
                        static_cast<size_t>(m) * n * sizeof(float));
}

// C = A·B — the batched transform X@PC (the path the reference left
// disabled, RapidsPCA.scala:172-185, enabled here).
TPUML_API int tpuml_pjrt_dot_nn_f32(const float* a, const float* b, int64_t m,
                                    int64_t k, int64_t n, float* out) {
  std::lock_guard<std::mutex> lk(g_mu);
  if (!g_client) {
    g_last_error = "pjrt client not initialized";
    return -1;
  }
  int h = cached_dot(false, m, k, n);
  if (h < 0) return -1;
  const float* inputs[2] = {a, b};
  const int64_t da[2] = {m, k}, db[2] = {k, n};
  const int64_t* dims[2] = {da, db};
  const int nd[2] = {2, 2};
  return execute_locked(h, inputs, dims, nd, 2, out,
                        static_cast<size_t>(m) * n * sizeof(float));
}

// Destroy the client (tests / clean shutdown; not required for exit).
TPUML_API void tpuml_pjrt_shutdown() {
  std::lock_guard<std::mutex> lk(g_mu);
  if (!g_client || !g_api) return;
  for (PJRT_LoadedExecutable* e : g_executables) {
    PJRT_LoadedExecutable_Destroy_Args d;
    std::memset(&d, 0, sizeof d);
    d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    d.executable = e;
    g_api->PJRT_LoadedExecutable_Destroy(&d);
  }
  g_executables.clear();
  g_kernel_cache.clear();
  PJRT_Client_Destroy_Args d;
  std::memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  d.client = g_client;
  g_api->PJRT_Client_Destroy(&d);
  g_client = nullptr;
  g_devices.clear();
}
