// Native dispatch core over the PJRT C API (SURVEY.md §7 design
// stance / hard-part 7; VERDICT r2 Missing #2).
//
// The reference's deploy path is C++ end-to-end: libmxnet.so executes
// compiled graphs with no interpreter in the loop.  This module is the
// TPU-native equivalent: it dlopens a PJRT plugin (libtpu.so on a TPU
// host), creates a client,
// compiles StableHLO/HLO programs, and executes them — all through the
// stable PJRT C ABI, no Python anywhere.  The frontends hand over
// serialized programs; after that, buffers live on device and the
// dispatch loop is pure C++.
//
// Scope: single-process, single addressable device per call (the
// deploy/predict shape).  Multi-device SPMD stays on the jax path —
// that split mirrors the reference, whose C predict API was also
// single-device while training ran the full engine.
//
// Built as its own libmxtpu_pjrt.so: the PJRT headers are vendored by
// the environment (tensorflow/include), and the core runtime must not
// depend on them.
#include <dlfcn.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

static thread_local std::string g_err;

extern "C" const char* MXTPUPjrtLastError() { return g_err.c_str(); }

#define ZERO_ARGS(T, a)            \
  T a;                             \
  std::memset(&a, 0, sizeof(a));   \
  a.struct_size = T##_STRUCT_SIZE

static bool ok(const PJRT_Api* api, PJRT_Error* err) {
  if (err == nullptr) return true;
  ZERO_ARGS(PJRT_Error_Message_Args, m);
  m.error = err;
  api->PJRT_Error_Message(&m);
  g_err.assign(m.message, m.message_size);
  ZERO_ARGS(PJRT_Error_Destroy_Args, d);
  d.error = err;
  api->PJRT_Error_Destroy(&d);
  return false;
}

static bool await_event(const PJRT_Api* api, PJRT_Event* ev) {
  if (ev == nullptr) return true;
  ZERO_ARGS(PJRT_Event_Await_Args, aw);
  aw.event = ev;
  PJRT_Error* err = api->PJRT_Event_Await(&aw);
  ZERO_ARGS(PJRT_Event_Destroy_Args, de);
  de.event = ev;
  api->PJRT_Event_Destroy(&de);
  return ok(api, err);
}

struct MXTPUPjrtClient {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  std::vector<PJRT_Device*> devices;
};

struct MXTPUPjrtExec {
  MXTPUPjrtClient* c = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  size_t num_outputs = 0;
};

struct MXTPUPjrtBuf {
  MXTPUPjrtClient* c = nullptr;
  PJRT_Buffer* buf = nullptr;
};

extern "C" void* MXTPUPjrtLoad(const char* plugin_path) {
  void* dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (dl == nullptr) {
    g_err = std::string("dlopen failed: ") + dlerror();
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
  if (get_api == nullptr) {
    g_err = "plugin exports no GetPjrtApi";
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (api == nullptr) {
    g_err = "GetPjrtApi returned null";
    dlclose(dl);
    return nullptr;
  }
  {
    ZERO_ARGS(PJRT_Plugin_Initialize_Args, ia);
    if (!ok(api, api->PJRT_Plugin_Initialize(&ia))) {
      dlclose(dl);
      return nullptr;
    }
  }
  ZERO_ARGS(PJRT_Client_Create_Args, ca);
  if (!ok(api, api->PJRT_Client_Create(&ca))) {
    dlclose(dl);
    return nullptr;
  }
  auto* h = new MXTPUPjrtClient;
  h->dl = dl;
  h->api = api;
  h->client = ca.client;
  ZERO_ARGS(PJRT_Client_AddressableDevices_Args, da);
  da.client = h->client;
  if (ok(api, api->PJRT_Client_AddressableDevices(&da))) {
    h->devices.assign(da.addressable_devices,
                      da.addressable_devices + da.num_addressable_devices);
  }
  return h;
}

extern "C" int MXTPUPjrtDeviceCount(void* hp) {
  return hp ? (int)((MXTPUPjrtClient*)hp)->devices.size() : 0;
}

extern "C" int MXTPUPjrtPlatformName(void* hp, char* out, int cap) {
  auto* h = (MXTPUPjrtClient*)hp;
  if (out == nullptr || cap < 1) {
    g_err = "platform name needs a buffer with cap >= 1";
    return -1;
  }
  ZERO_ARGS(PJRT_Client_PlatformName_Args, pa);
  pa.client = h->client;
  if (!ok(h->api, h->api->PJRT_Client_PlatformName(&pa))) return -1;
  int len = (int)pa.platform_name_size;
  int n = len < cap - 1 ? len : cap - 1;
  std::memcpy(out, pa.platform_name, n);
  out[n] = 0;
  return len;  // full length: truncation is detectable (snprintf-style)
}

extern "C" void MXTPUPjrtFree(void* hp) {
  auto* h = (MXTPUPjrtClient*)hp;
  if (h == nullptr) return;
  if (h->client != nullptr) {
    ZERO_ARGS(PJRT_Client_Destroy_Args, da);
    da.client = h->client;
    h->api->PJRT_Client_Destroy(&da);
  }
  // NOTE: the plugin .so stays mapped (dlclose after client teardown
  // is unsafe with some plugins' background threads)
  delete h;
}

extern "C" void* MXTPUPjrtCompile(void* hp, const char* code,
                                  int64_t code_size, const char* format,
                                  const char* options,
                                  int64_t options_size) {
  auto* h = (MXTPUPjrtClient*)hp;
  ZERO_ARGS(PJRT_Program, prog);
  prog.code = const_cast<char*>(code);
  prog.code_size = (size_t)code_size;
  prog.format = format;
  prog.format_size = std::strlen(format);
  ZERO_ARGS(PJRT_Client_Compile_Args, ca);
  ca.client = h->client;
  ca.program = &prog;
  ca.compile_options = options;
  ca.compile_options_size = (size_t)options_size;
  if (!ok(h->api, h->api->PJRT_Client_Compile(&ca))) return nullptr;
  auto* e = new MXTPUPjrtExec;
  e->c = h;
  e->exec = ca.executable;
  // the output count sizes Execute's output array — failing to learn
  // it must fail the compile, or the plugin would later write real
  // output pointers past a zero-length array
  bool got_outputs = false;
  ZERO_ARGS(PJRT_LoadedExecutable_GetExecutable_Args, ga);
  ga.loaded_executable = e->exec;
  if (ok(h->api, h->api->PJRT_LoadedExecutable_GetExecutable(&ga))) {
    ZERO_ARGS(PJRT_Executable_NumOutputs_Args, na);
    na.executable = ga.executable;
    if (ok(h->api, h->api->PJRT_Executable_NumOutputs(&na))) {
      e->num_outputs = na.num_outputs;
      got_outputs = true;
    }
    ZERO_ARGS(PJRT_Executable_Destroy_Args, xd);
    xd.executable = ga.executable;
    h->api->PJRT_Executable_Destroy(&xd);
  }
  if (!got_outputs) {
    std::string saved = g_err;
    ZERO_ARGS(PJRT_LoadedExecutable_Destroy_Args, ld);
    ld.executable = e->exec;
    h->api->PJRT_LoadedExecutable_Destroy(&ld);
    delete e;
    g_err = "could not determine executable output count: " + saved;
    return nullptr;
  }
  return e;
}

extern "C" int MXTPUPjrtExecNumOutputs(void* ep) {
  return ep ? (int)((MXTPUPjrtExec*)ep)->num_outputs : -1;
}

extern "C" void MXTPUPjrtExecFree(void* ep) {
  auto* e = (MXTPUPjrtExec*)ep;
  if (e == nullptr) return;
  ZERO_ARGS(PJRT_LoadedExecutable_Destroy_Args, da);
  da.executable = e->exec;
  e->c->api->PJRT_LoadedExecutable_Destroy(&da);
  delete e;
}

extern "C" void* MXTPUPjrtBufferFromHost(void* hp, const void* data,
                                         int dtype, const int64_t* dims,
                                         int ndims, int device_index) {
  auto* h = (MXTPUPjrtClient*)hp;
  if (device_index < 0 || device_index >= (int)h->devices.size()) {
    g_err = "device index out of range";
    return nullptr;
  }
  ZERO_ARGS(PJRT_Client_BufferFromHostBuffer_Args, ba);
  ba.client = h->client;
  ba.data = data;
  ba.type = (PJRT_Buffer_Type)dtype;
  ba.dims = dims;
  ba.num_dims = (size_t)ndims;
  ba.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  ba.device = h->devices[device_index];
  if (!ok(h->api, h->api->PJRT_Client_BufferFromHostBuffer(&ba)))
    return nullptr;
  // once this event fires the caller may free/reuse the host memory
  if (!await_event(h->api, ba.done_with_host_buffer)) {
    ZERO_ARGS(PJRT_Buffer_Destroy_Args, bd);
    bd.buffer = ba.buffer;
    h->api->PJRT_Buffer_Destroy(&bd);
    return nullptr;
  }
  auto* b = new MXTPUPjrtBuf;
  b->c = h;
  b->buf = ba.buffer;
  return b;
}

extern "C" void MXTPUPjrtBufferFree(void* bp) {
  auto* b = (MXTPUPjrtBuf*)bp;
  if (b == nullptr) return;
  ZERO_ARGS(PJRT_Buffer_Destroy_Args, da);
  da.buffer = b->buf;
  b->c->api->PJRT_Buffer_Destroy(&da);
  delete b;
}

extern "C" int MXTPUPjrtBufferType(void* bp) {
  auto* b = (MXTPUPjrtBuf*)bp;
  ZERO_ARGS(PJRT_Buffer_ElementType_Args, ta);
  ta.buffer = b->buf;
  if (!ok(b->c->api, b->c->api->PJRT_Buffer_ElementType(&ta))) return -1;
  return (int)ta.type;
}

extern "C" int MXTPUPjrtBufferDims(void* bp, int64_t* out, int cap) {
  auto* b = (MXTPUPjrtBuf*)bp;
  ZERO_ARGS(PJRT_Buffer_Dimensions_Args, da);
  da.buffer = b->buf;
  if (!ok(b->c->api, b->c->api->PJRT_Buffer_Dimensions(&da))) return -1;
  if (out == nullptr) return (int)da.num_dims;  // rank query
  if ((int)da.num_dims > cap) {
    g_err = "dims capacity too small";
    return -1;
  }
  for (size_t i = 0; i < da.num_dims; ++i) out[i] = da.dims[i];
  return (int)da.num_dims;
}

extern "C" int64_t MXTPUPjrtBufferToHost(void* bp, void* dst,
                                         int64_t dst_size) {
  auto* b = (MXTPUPjrtBuf*)bp;
  const PJRT_Api* api = b->c->api;
  ZERO_ARGS(PJRT_Buffer_ToHostBuffer_Args, ta);
  ta.src = b->buf;
  ta.dst = nullptr;  // size query first
  if (!ok(api, api->PJRT_Buffer_ToHostBuffer(&ta))) return -1;
  if (dst == nullptr) return (int64_t)ta.dst_size;
  if ((int64_t)ta.dst_size > dst_size) {
    g_err = "destination too small";
    return -1;
  }
  int64_t need = (int64_t)ta.dst_size;
  ZERO_ARGS(PJRT_Buffer_ToHostBuffer_Args, ca);
  ca.src = b->buf;
  ca.dst = dst;
  ca.dst_size = (size_t)need;
  if (!ok(api, api->PJRT_Buffer_ToHostBuffer(&ca))) return -1;
  if (!await_event(api, ca.event)) return -1;
  return need;
}

// Execute on ONE device: n_args device buffers in, the executable's
// outputs appear as new buffer handles in out_bufs (caller provides
// capacity MXTPUPjrtExecNumOutputs).  Blocks until device completion —
// async pipelining is the caller's loop structure, exactly like the
// reference's predictor.
extern "C" int MXTPUPjrtExecute(void* ep, void** arg_bufs, int n_args,
                                void** out_bufs, int out_cap) {
  auto* e = (MXTPUPjrtExec*)ep;
  const PJRT_Api* api = e->c->api;
  if (out_cap < (int)e->num_outputs) {
    g_err = "output capacity too small";
    return -1;
  }
  std::vector<PJRT_Buffer*> args((size_t)n_args);
  for (int i = 0; i < n_args; ++i)
    args[i] = ((MXTPUPjrtBuf*)arg_bufs[i])->buf;
  PJRT_Buffer* const* arg_list = args.data();
  std::vector<PJRT_Buffer*> outs(e->num_outputs, nullptr);
  PJRT_Buffer** out_list = outs.data();
  PJRT_Event* dev_event = nullptr;
  ZERO_ARGS(PJRT_ExecuteOptions, opts);
  ZERO_ARGS(PJRT_LoadedExecutable_Execute_Args, xa);
  xa.executable = e->exec;
  xa.options = &opts;
  xa.argument_lists = &arg_list;
  xa.num_devices = 1;
  xa.num_args = (size_t)n_args;
  xa.output_lists = &out_list;
  xa.device_complete_events = &dev_event;
  if (!ok(api, api->PJRT_LoadedExecutable_Execute(&xa))) return -1;
  if (!await_event(api, dev_event)) {
    // device-side failure: the plugin already handed us output
    // buffers — free them or every failed step leaks HBM
    for (PJRT_Buffer* o : outs) {
      if (o == nullptr) continue;
      ZERO_ARGS(PJRT_Buffer_Destroy_Args, bd);
      bd.buffer = o;
      api->PJRT_Buffer_Destroy(&bd);
    }
    return -1;
  }
  for (size_t i = 0; i < e->num_outputs; ++i) {
    auto* b = new MXTPUPjrtBuf;
    b->c = e->c;
    b->buf = outs[i];
    out_bufs[i] = b;
  }
  return (int)e->num_outputs;
}

// ---------------------------------------------------------------------------
// Predict convenience over the core (reference c_predict_api.h shape):
// load an MXTPUSHLO2 bundle from disk, compile it, run the
// set-input/forward/get-output loop — every line C++, no interpreter.
// The bundle layout is written by mxnet_tpu.deploy.export_stablehlo:
//   "MXTPUSHLO2" | u64 n_code | u64 n_blob | code | blob
// (only the raw StableHLO `code` section is read here).
// ---------------------------------------------------------------------------
#include <cstdio>

static const char kBundleMagic[] = "MXTPUSHLO2";

extern "C" void* MXTPUPjrtPredictCreate(void* client,
                                        const char* bundle_path) {
  FILE* f = std::fopen(bundle_path, "rb");
  if (f == nullptr) {
    g_err = std::string("cannot open bundle: ") + bundle_path;
    return nullptr;
  }
  char magic[sizeof(kBundleMagic) - 1];
  uint64_t lens[2];
  std::vector<char> code;
  bool ok_read =
      std::fread(magic, 1, sizeof(magic), f) == sizeof(magic) &&
      std::memcmp(magic, kBundleMagic, sizeof(magic)) == 0 &&
      std::fread(lens, sizeof(uint64_t), 2, f) == 2;
  if (ok_read) {
    // bound n_code by the actual file size: a corrupt length field
    // must produce an error, not a std::bad_alloc flying across the
    // extern "C" boundary
    long here = std::ftell(f);
    std::fseek(f, 0, SEEK_END);
    long fsize = std::ftell(f);
    std::fseek(f, here, SEEK_SET);
    ok_read = here >= 0 && fsize >= here &&
              lens[0] <= (uint64_t)(fsize - here);
  }
  if (ok_read) {
    code.resize(lens[0]);
    ok_read = std::fread(code.data(), 1, code.size(), f) == code.size();
  }
  std::fclose(f);
  if (!ok_read) {
    g_err = std::string("not a valid MXTPUSHLO2 bundle: ") + bundle_path;
    return nullptr;
  }
  // empty options = proto defaults.  Plugins that need non-default
  // CompileOptions (device assignments etc.) should read the bundle
  // with read_stablehlo and call MXTPUPjrtCompile with explicit
  // serialized options (the Python path passes jaxlib defaults).
  return MXTPUPjrtCompile(client, code.data(), (int64_t)code.size(),
                          "mlir", "", 0);
}
