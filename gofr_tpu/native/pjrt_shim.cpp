// PJRT C-API loader/executor shim — the framework's native device runtime.
//
// The reference (nidhey27/gofr) is pure Go with no native code; the TPU
// build's north star instead mandates a native binding that "wraps the
// PJRT C API" (BASELINE.json). This file is that binding: a thin C++
// layer that dlopens any PJRT plugin (libtpu.so / a test plugin),
// negotiates the versioned function-pointer table via
// GetPjrtApi(), and exposes a flat C ABI that gofr_tpu/native/pjrt.py
// drives through ctypes — client creation with named-value options,
// StableHLO/MLIR compilation, host<->device transfers, and synchronous
// execution with event await.
//
// Design notes:
//  * Every PJRT arg struct is stack-allocated, zeroed, and stamped with
//    the header's *_STRUCT_SIZE so older plugins (which check
//    struct_size >= their compiled-in minimum) accept newer callers.
//  * All entry points funnel PJRT_Error through gofr_err(): message is
//    copied into the caller's buffer, then the error is destroyed —
//    nothing leaks across the ctypes boundary.
//  * The shim is deliberately single-device per call (the serving
//    engine's unit of work); multi-chip goes through jit/GSPMD, not
//    this binding.
//
// Built by gofr_tpu.native.build_and_load with -I<tensorflow include>
// for xla/pjrt/c/pjrt_c_api.h (the public, versioned C API header).

#include <dlfcn.h>
#include <cstdint>
#include <cstring>
#include <cstdio>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

// Copy a PJRT_Error's message into (err, errcap), destroy the error.
// Returns true iff there was an error.
bool gofr_err(const PJRT_Api* api, PJRT_Error* e, char* err, size_t errcap) {
  if (e == nullptr) {
    if (err && errcap) err[0] = '\0';
    return false;
  }
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = e;
  api->PJRT_Error_Message(&margs);
  if (err && errcap) {
    size_t n = margs.message_size < errcap - 1 ? margs.message_size : errcap - 1;
    std::memcpy(err, margs.message, n);
    err[n] = '\0';
  }
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = e;
  api->PJRT_Error_Destroy(&dargs);
  return true;
}

// Await + destroy an event, folding its error into (err, errcap).
bool gofr_await(const PJRT_Api* api, PJRT_Event* ev, char* err, size_t errcap) {
  if (ev == nullptr) return false;
  PJRT_Event_Await_Args aargs;
  std::memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = ev;
  PJRT_Error* e = api->PJRT_Event_Await(&aargs);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  api->PJRT_Event_Destroy(&dargs);
  return gofr_err(api, e, err, errcap);
}

PJRT_Device* gofr_first_device(const PJRT_Api* api, PJRT_Client* client,
                               char* err, size_t errcap) {
  PJRT_Client_AddressableDevices_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  args.client = client;
  if (gofr_err(api, api->PJRT_Client_AddressableDevices(&args), err, errcap))
    return nullptr;
  if (args.num_addressable_devices == 0) {
    std::snprintf(err, errcap, "no addressable devices");
    return nullptr;
  }
  return args.addressable_devices[0];
}

}  // namespace

extern "C" {

// dlopen the plugin, resolve GetPjrtApi, run PJRT_Plugin_Initialize.
// Returns the PJRT_Api* (opaque to Python) or null with err filled.
void* gofr_pjrt_load(const char* so_path, char* err, size_t errcap) {
  void* handle = dlopen(so_path, RTLD_NOW | RTLD_GLOBAL);
  if (!handle) {
    std::snprintf(err, errcap, "dlopen failed: %s", dlerror());
    return nullptr;
  }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetPjrtApiFn>(dlsym(handle, "GetPjrtApi"));
  if (!get_api) {
    std::snprintf(err, errcap, "GetPjrtApi not found: %s", dlerror());
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (!api) {
    std::snprintf(err, errcap, "GetPjrtApi returned null");
    return nullptr;
  }
  PJRT_Plugin_Initialize_Args init;
  std::memset(&init, 0, sizeof(init));
  init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (api->PJRT_Plugin_Initialize &&
      gofr_err(api, api->PJRT_Plugin_Initialize(&init), err, errcap))
    return nullptr;
  return const_cast<PJRT_Api*>(api);
}

void gofr_pjrt_api_version(void* vapi, int* major, int* minor) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  *major = api->pjrt_api_version.major_version;
  *minor = api->pjrt_api_version.minor_version;
}

// kinds[i]: 0 = string (svals[i]), 1 = int64 (ivals[i]), 2 = bool (ivals[i]).
void* gofr_pjrt_client_create(void* vapi, const char** keys,
                              const char** svals, const int64_t* ivals,
                              const int* kinds, size_t n_options,
                              char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_NamedValue opts[64];
  if (n_options > 64) {
    std::snprintf(err, errcap, "too many options (%zu > 64)", n_options);
    return nullptr;
  }
  std::memset(opts, 0, sizeof(opts));
  for (size_t i = 0; i < n_options; ++i) {
    opts[i].struct_size = PJRT_NamedValue_STRUCT_SIZE;
    opts[i].name = keys[i];
    opts[i].name_size = std::strlen(keys[i]);
    if (kinds[i] == 0) {
      opts[i].type = PJRT_NamedValue_kString;
      opts[i].string_value = svals[i];
      opts[i].value_size = std::strlen(svals[i]);
    } else if (kinds[i] == 2) {
      opts[i].type = PJRT_NamedValue_kBool;
      opts[i].bool_value = ivals[i] != 0;
      opts[i].value_size = 1;
    } else {
      opts[i].type = PJRT_NamedValue_kInt64;
      opts[i].int64_value = ivals[i];
      opts[i].value_size = 1;
    }
  }
  PJRT_Client_Create_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  args.create_options = opts;
  args.num_options = n_options;
  if (gofr_err(api, api->PJRT_Client_Create(&args), err, errcap))
    return nullptr;
  return args.client;
}

void gofr_pjrt_client_destroy(void* vapi, void* vclient) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_Client_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(vclient);
  gofr_err(api, api->PJRT_Client_Destroy(&args), nullptr, 0);
}

long long gofr_pjrt_device_count(void* vapi, void* vclient,
                                 char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_Client_AddressableDevices_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(vclient);
  if (gofr_err(api, api->PJRT_Client_AddressableDevices(&args), err, errcap))
    return -1;
  return static_cast<long long>(args.num_addressable_devices);
}

// Copies the platform name into (out, outcap); returns its length or -1.
long long gofr_pjrt_platform_name(void* vapi, void* vclient, char* out,
                                  size_t outcap, char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_Client_PlatformName_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(vclient);
  if (gofr_err(api, api->PJRT_Client_PlatformName(&args), err, errcap))
    return -1;
  size_t n = args.platform_name_size < outcap - 1 ? args.platform_name_size
                                                  : outcap - 1;
  std::memcpy(out, args.platform_name, n);
  out[n] = '\0';
  return static_cast<long long>(args.platform_name_size);
}

// Compile `code` (format "mlir" for StableHLO text/bytecode, or "hlo")
// with a serialized CompileOptionsProto. Returns PJRT_LoadedExecutable*.
void* gofr_pjrt_compile(void* vapi, void* vclient, const char* code,
                        size_t code_size, const char* format,
                        const char* copts, size_t copts_size,
                        char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(code);
  program.code_size = code_size;
  program.format = format;
  program.format_size = std::strlen(format);
  PJRT_Client_Compile_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  args.client = static_cast<PJRT_Client*>(vclient);
  args.program = &program;
  args.compile_options = copts;
  args.compile_options_size = copts_size;
  if (gofr_err(api, api->PJRT_Client_Compile(&args), err, errcap))
    return nullptr;
  return args.executable;
}

void gofr_pjrt_executable_destroy(void* vapi, void* vexec) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_LoadedExecutable_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  args.executable = static_cast<PJRT_LoadedExecutable*>(vexec);
  gofr_err(api, api->PJRT_LoadedExecutable_Destroy(&args), nullptr, 0);
}

long long gofr_pjrt_num_outputs(void* vapi, void* vexec,
                                char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = static_cast<PJRT_LoadedExecutable*>(vexec);
  if (gofr_err(api, api->PJRT_LoadedExecutable_GetExecutable(&gargs), err,
               errcap))
    return -1;
  PJRT_Executable_NumOutputs_Args nargs;
  std::memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  bool failed =
      gofr_err(api, api->PJRT_Executable_NumOutputs(&nargs), err, errcap);
  // The wrapper executable returned by GetExecutable is caller-owned
  // (pjrt_c_api.h contract) — destroy it or every call leaks one.
  PJRT_Executable_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
  dargs.executable = gargs.executable;
  if (api->PJRT_Executable_Destroy)
    gofr_err(api, api->PJRT_Executable_Destroy(&dargs), nullptr, 0);
  if (failed) return -1;
  return static_cast<long long>(nargs.num_outputs);
}

// Synchronous host->device transfer onto the first addressable device.
// dtype is a PJRT_Buffer_Type value. Returns PJRT_Buffer*.
void* gofr_pjrt_buffer_from_host(void* vapi, void* vclient, const void* data,
                                 int dtype, const int64_t* dims,
                                 size_t num_dims, char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  auto* client = static_cast<PJRT_Client*>(vclient);
  PJRT_Device* device = gofr_first_device(api, client, err, errcap);
  if (!device) return nullptr;
  PJRT_Client_BufferFromHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  args.client = client;
  args.data = data;
  args.type = static_cast<PJRT_Buffer_Type>(dtype);
  args.dims = dims;
  args.num_dims = num_dims;
  args.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  args.device = device;
  if (gofr_err(api, api->PJRT_Client_BufferFromHostBuffer(&args), err, errcap))
    return nullptr;
  if (gofr_await(api, args.done_with_host_buffer, err, errcap)) {
    // transfer failed; buffer is unusable
    return nullptr;
  }
  return args.buffer;
}

void gofr_pjrt_buffer_destroy(void* vapi, void* vbuf) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_Buffer_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(vbuf);
  gofr_err(api, api->PJRT_Buffer_Destroy(&args), nullptr, 0);
}

long long gofr_pjrt_buffer_ndims(void* vapi, void* vbuf, int64_t* dims,
                                 size_t cap, char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_Buffer_Dimensions_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(vbuf);
  if (gofr_err(api, api->PJRT_Buffer_Dimensions(&args), err, errcap))
    return -1;
  size_t n = args.num_dims < cap ? args.num_dims : cap;
  for (size_t i = 0; i < n; ++i) dims[i] = args.dims[i];
  return static_cast<long long>(args.num_dims);
}

int gofr_pjrt_buffer_dtype(void* vapi, void* vbuf) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  PJRT_Buffer_ElementType_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(vbuf);
  if (api->PJRT_Buffer_ElementType(&args) != nullptr) return -1;
  return static_cast<int>(args.type);
}

// Device->host: two-phase (dst=null queries size). Awaits completion.
// An explicit dense major-to-minor host layout is requested — on TPU the
// source buffer's own layout is tiled, and copying it raw would hand
// Python a tile-permuted byte stream (ndims is needed for that layout,
// so the caller passes it; 0 = let the plugin pick, for rank-0/opaque).
long long gofr_pjrt_buffer_to_host(void* vapi, void* vbuf, size_t ndims,
                                   void* dst, size_t dst_size,
                                   char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  int64_t minor_to_major[16];
  PJRT_Buffer_MemoryLayout layout;
  std::memset(&layout, 0, sizeof(layout));
  PJRT_Buffer_ToHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = static_cast<PJRT_Buffer*>(vbuf);
  if (ndims > 0 && ndims <= 16) {
    layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
    layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
    layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
    for (size_t i = 0; i < ndims; ++i)
      minor_to_major[i] = static_cast<int64_t>(ndims - 1 - i);
    layout.tiled.minor_to_major = minor_to_major;
    layout.tiled.minor_to_major_size = ndims;
    args.host_layout = &layout;
  }
  args.dst = dst;
  args.dst_size = dst_size;
  if (gofr_err(api, api->PJRT_Buffer_ToHostBuffer(&args), err, errcap))
    return -1;
  if (dst != nullptr && gofr_await(api, args.event, err, errcap)) return -1;
  return static_cast<long long>(args.dst_size);
}

// Single-device synchronous execute: in[num_args] -> out[noutcap].
// Returns the number of outputs written, or -1. nout_hint skips the
// per-call GetExecutable/NumOutputs round-trip when the caller cached the
// count at compile time (pass -1 to derive it here).
long long gofr_pjrt_execute(void* vapi, void* vexec, void** in, size_t num_args,
                            void** out, size_t noutcap,
                            long long nout_hint,
                            char* err, size_t errcap) {
  auto* api = static_cast<const PJRT_Api*>(vapi);
  long long nout = nout_hint >= 0
      ? nout_hint
      : gofr_pjrt_num_outputs(vapi, vexec, err, errcap);
  if (nout < 0) return -1;
  if (static_cast<size_t>(nout) > noutcap) {
    std::snprintf(err, errcap, "output capacity %zu < %lld", noutcap, nout);
    return -1;
  }
  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_Buffer** arg_list = reinterpret_cast<PJRT_Buffer**>(in);
  PJRT_Buffer* const* const arg_lists[1] = {arg_list};
  PJRT_Buffer* outputs[256];
  std::memset(outputs, 0, sizeof(outputs));
  PJRT_Buffer** output_lists[1] = {outputs};
  PJRT_Event* done[1] = {nullptr};
  if (nout > 256) {
    std::snprintf(err, errcap, "more than 256 outputs (%lld)", nout);
    return -1;
  }

  PJRT_LoadedExecutable_Execute_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  args.executable = static_cast<PJRT_LoadedExecutable*>(vexec);
  args.options = &opts;
  args.argument_lists = arg_lists;
  args.num_devices = 1;
  args.num_args = num_args;
  args.output_lists = output_lists;
  args.device_complete_events = done;
  if (gofr_err(api, api->PJRT_LoadedExecutable_Execute(&args), err, errcap))
    return -1;
  if (gofr_await(api, done[0], err, errcap)) {
    // execution failed after Execute populated the output buffers: destroy
    // them or every failed execute leaks nout device allocations
    for (long long i = 0; i < nout; ++i)
      if (outputs[i]) gofr_pjrt_buffer_destroy(vapi, outputs[i]);
    return -1;
  }
  for (long long i = 0; i < nout; ++i) out[i] = outputs[i];
  return nout;
}

}  // extern "C"
