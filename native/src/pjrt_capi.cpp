// C inference over the PJRT C API — the TPU-production path.
//
// Reference analog: paddle/capi driving the C++ engine on device
// (capi/gradient_machine.h:36-112). Here the engine is the platform's
// PJRT plugin (libtpu.so on TPU hosts; any GetPjrtApi .so works): the
// .ptpj artifact (export.export_pjrt_model) carries the StableHLO module
// with weights baked in + serialized CompileOptions, this file dlopens
// the plugin, compiles, and executes — no Python, no jax, no XLA linked
// into the embedder's process. SURVEY §7 item 11 ("C ABI over PJRT").
//
// Sibling paths: aot_runtime.cpp (CPU embedded, no plugin needed),
// capi.cpp (embedded CPython, full graph coverage).
//
// NOTE: the CPU sandbox has libtpu.so (the only GetPjrtApi provider) but
// no local TPU, so CI exercises plugin loading, artifact parsing, API
// versioning, and the graceful-failure path; the execute path runs on
// real TPU hosts (ptpu_pjrt self-test gated by PTPU_PJRT_PLUGIN).

#include <dlfcn.h>

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

thread_local std::string g_last_error;

void set_error(std::string msg) { g_last_error = std::move(msg); }

// consume + destroy a PJRT_Error; returns true if there WAS an error
bool take_error(const PJRT_Api* api, PJRT_Error* err, const char* where) {
  if (err == nullptr) return false;
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  set_error(std::string(where) + ": " +
            std::string(margs.message, margs.message_size));
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return true;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, const char* where) {
  if (!ev) return true;
  PJRT_Event_Await_Args aargs;
  memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = ev;
  PJRT_Error* err = api->PJRT_Event_Await(&aargs);
  PJRT_Event_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  api->PJRT_Event_Destroy(&dargs);
  return !take_error(api, err, where);
}

struct InputSpec {
  std::string name;
  uint8_t dtype = 0;  // 0 = f32, 1 = i32
  uint8_t rank = 2;   // 1 ([batch]) or 2 ([batch, dim])
  int64_t batch = 0;
  int64_t dim = 0;    // 1 for rank-1 specs
};

struct Model {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  size_t num_outputs = 0;
  std::vector<InputSpec> inputs;
  // shared-param instances (ptpu_pjrt_create_shared) hold the same Model
  // (one compiled executable, weights baked in on device once)
  std::atomic<int> refs{1};
};

bool read_exact(FILE* f, void* dst, size_t n) {
  return fread(dst, 1, n, f) == n;
}

template <typename T>
bool rd(FILE* f, T* v) { return read_exact(f, v, sizeof(T)); }

// Parse the .ptpj container (export.export_pjrt_model).
bool parse_ptpj(const char* path, std::vector<InputSpec>* inputs,
                uint32_t* n_outputs, std::string* mlir, std::string* opts) {
  FILE* f = fopen(path, "rb");
  if (!f) {
    set_error(std::string("cannot open ") + path);
    return false;
  }
  auto fail = [&](const char* why) {
    set_error(std::string("bad .ptpj: ") + why);
    fclose(f);
    return false;
  };
  char magic[4];
  uint32_t version = 0, ni = 0;
  if (!read_exact(f, magic, 4) || memcmp(magic, "PTPJ", 4) != 0)
    return fail("magic");
  if (!rd(f, &version) || (version != 1 && version != 2))
    return fail("version");
  if (!rd(f, &ni)) return fail("inputs");
  for (uint32_t i = 0; i < ni; ++i) {
    uint16_t nl = 0;
    if (!rd(f, &nl)) return fail("name len");
    InputSpec spec;
    spec.name.resize(nl);
    if (nl && !read_exact(f, spec.name.data(), nl)) return fail("name");
    uint8_t dtype = 0, rank = 0;
    if (!rd(f, &dtype) || !rd(f, &rank)) return fail("spec");
    // v1 artifacts only ever declared f32 rank-2; v2 adds i32 rank-1
    // (integer/embedding feeds) so the spec matches the module signature
    if (version == 1 && (dtype != 0 || rank != 2)) return fail("spec");
    if (dtype > 1 || rank < 1 || rank > 2) return fail("spec");
    spec.dtype = dtype;
    spec.rank = rank;
    if (rank == 2) {
      int64_t dims[2];
      if (!read_exact(f, dims, sizeof(dims))) return fail("dims");
      spec.batch = dims[0];
      spec.dim = dims[1];
    } else {
      int64_t d0 = 0;
      if (!rd(f, &d0)) return fail("dims");
      spec.batch = d0;
      spec.dim = 1;
    }
    inputs->push_back(std::move(spec));
  }
  if (!rd(f, n_outputs)) return fail("outputs");
  uint64_t mlir_len = 0, opts_len = 0;
  if (!rd(f, &mlir_len)) return fail("mlir len");
  mlir->resize(mlir_len);
  if (mlir_len && !read_exact(f, mlir->data(), mlir_len))
    return fail("mlir");
  if (!rd(f, &opts_len)) return fail("opts len");
  opts->resize(opts_len);
  if (opts_len && !read_exact(f, opts->data(), opts_len))
    return fail("opts");
  fclose(f);
  return true;
}

void destroy_model(Model* m) {
  if (!m) return;
  if (m->api) {
    if (m->exec) {
      PJRT_LoadedExecutable_Destroy_Args args;
      memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      args.executable = m->exec;
      take_error(m->api, m->api->PJRT_LoadedExecutable_Destroy(&args),
                 "exec destroy");
    }
    if (m->client) {
      PJRT_Client_Destroy_Args args;
      memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      args.client = m->client;
      take_error(m->api, m->api->PJRT_Client_Destroy(&args),
                 "client destroy");
    }
  }
  if (m->dl) dlclose(m->dl);
  delete m;
}

}  // namespace

extern "C" {

const char* ptpu_pjrt_last_error(void) { return g_last_error.c_str(); }

// Load plugin + artifact, create the client, compile the module.
// Returns a handle or nullptr (ptpu_pjrt_last_error explains).
void* ptpu_pjrt_load(const char* model_path, const char* plugin_path) {
  auto* m = new Model();
  m->dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!m->dl) {
    set_error(std::string("dlopen ") + plugin_path + ": " + dlerror());
    destroy_model(m);
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(m->dl, "GetPjrtApi"));
  if (!get_api) {
    set_error("plugin exports no GetPjrtApi");
    destroy_model(m);
    return nullptr;
  }
  m->api = get_api();
  if (!m->api || m->api->pjrt_api_version.major_version != PJRT_API_MAJOR) {
    set_error("PJRT API major version mismatch");
    destroy_model(m);
    return nullptr;
  }

  std::string mlir, opts;
  uint32_t n_outputs = 0;
  if (!parse_ptpj(model_path, &m->inputs, &n_outputs, &mlir, &opts)) {
    destroy_model(m);
    return nullptr;
  }

  {
    PJRT_Plugin_Initialize_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (take_error(m->api, m->api->PJRT_Plugin_Initialize(&args),
                   "plugin init")) {
      destroy_model(m);
      return nullptr;
    }
  }
  {
    PJRT_Client_Create_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    if (take_error(m->api, m->api->PJRT_Client_Create(&args),
                   "client create")) {
      destroy_model(m);
      return nullptr;
    }
    m->client = args.client;
  }
  {
    PJRT_Program program;
    memset(&program, 0, sizeof(program));
    program.struct_size = PJRT_Program_STRUCT_SIZE;
    program.code = mlir.data();
    program.code_size = mlir.size();
    static const char kFormat[] = "mlir";
    program.format = kFormat;
    program.format_size = sizeof(kFormat) - 1;

    PJRT_Client_Compile_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    args.client = m->client;
    args.program = &program;
    args.compile_options = opts.data();
    args.compile_options_size = opts.size();
    if (take_error(m->api, m->api->PJRT_Client_Compile(&args), "compile")) {
      destroy_model(m);
      return nullptr;
    }
    m->exec = args.executable;
  }
  {
    PJRT_LoadedExecutable_GetExecutable_Args gargs;
    memset(&gargs, 0, sizeof(gargs));
    gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    gargs.loaded_executable = m->exec;
    if (take_error(m->api, m->api->PJRT_LoadedExecutable_GetExecutable(&gargs),
                   "get executable")) {
      destroy_model(m);
      return nullptr;
    }
    PJRT_Executable_NumOutputs_Args nargs;
    memset(&nargs, 0, sizeof(nargs));
    nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    nargs.executable = gargs.executable;
    if (take_error(m->api, m->api->PJRT_Executable_NumOutputs(&nargs),
                   "num outputs")) {
      destroy_model(m);
      return nullptr;
    }
    m->num_outputs = nargs.num_outputs;
    PJRT_Executable_Destroy_Args dargs;
    memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
    dargs.executable = gargs.executable;
    take_error(m->api, m->api->PJRT_Executable_Destroy(&dargs),
               "executable destroy");
  }
  return m;
}

}  // extern "C"

namespace {

// Shared single-input execute path. 0 ok, -2 capacity, -3 shape/dtype
// mismatch, -4 contract (not single-input / wrong name), -1 runtime
// failure.
int pjrt_infer_impl(Model* m, const char* input_name, const void* data,
                    uint8_t dtype_code, int64_t batch, int64_t dim,
                    float* out, int64_t out_capacity, int64_t* out_rows,
                    int64_t* out_cols) {
  if (!m || !m->exec) return -1;
  if (m->inputs.size() != 1 || m->inputs[0].name != input_name) return -4;
  const InputSpec& spec = m->inputs[0];
  if (spec.dtype != dtype_code) return -3;
  if (spec.batch != batch || spec.dim != dim) return -3;

  const PJRT_Api* api = m->api;
  // addressable device 0
  PJRT_Device* device = nullptr;
  {
    PJRT_Client_AddressableDevices_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    args.client = m->client;
    if (take_error(api, api->PJRT_Client_AddressableDevices(&args),
                   "addressable devices"))
      return -1;
    if (args.num_addressable_devices == 0) {
      set_error("no addressable devices");
      return -1;
    }
    device = args.addressable_devices[0];
  }

  // host -> device
  PJRT_Buffer* in_buf = nullptr;
  {
    int64_t dims[2] = {batch, dim};
    PJRT_Client_BufferFromHostBuffer_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    args.client = m->client;
    args.data = data;
    args.type = spec.dtype == 1 ? PJRT_Buffer_Type_S32 : PJRT_Buffer_Type_F32;
    args.dims = dims;
    args.num_dims = spec.rank;
    args.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    args.device = device;
    if (take_error(api, api->PJRT_Client_BufferFromHostBuffer(&args),
                   "h2d"))
      return -1;
    in_buf = args.buffer;
    if (!await_event(api, args.done_with_host_buffer, "h2d event")) {
      // fallthrough: buffer still destroyed below on error path
    }
  }

  auto destroy_buffer = [&](PJRT_Buffer* b) {
    if (!b) return;
    PJRT_Buffer_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    args.buffer = b;
    take_error(api, api->PJRT_Buffer_Destroy(&args), "buffer destroy");
  };

  // execute
  std::vector<PJRT_Buffer*> outputs(m->num_outputs, nullptr);
  {
    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_Buffer* const arg_list[] = {in_buf};
    PJRT_Buffer* const* const arg_lists[] = {arg_list};
    PJRT_Buffer** output_lists[] = {outputs.data()};
    PJRT_Event* done = nullptr;

    PJRT_LoadedExecutable_Execute_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    args.executable = m->exec;
    args.options = &opts;
    args.argument_lists = arg_lists;
    args.num_devices = 1;
    args.num_args = 1;
    args.output_lists = output_lists;
    args.device_complete_events = &done;
    args.execute_device = device;
    if (take_error(api, api->PJRT_LoadedExecutable_Execute(&args),
                   "execute")) {
      destroy_buffer(in_buf);
      return -1;
    }
    if (!await_event(api, done, "execute event")) {
      destroy_buffer(in_buf);
      for (auto* b : outputs) destroy_buffer(b);
      return -1;
    }
  }
  destroy_buffer(in_buf);

  // first output -> host
  int rc = -1;
  {
    PJRT_Buffer* out_buf = outputs[0];
    PJRT_Buffer_Dimensions_Args dargs;
    memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    dargs.buffer = out_buf;
    if (!take_error(api, api->PJRT_Buffer_Dimensions(&dargs), "dims")) {
      int64_t rows = dargs.num_dims > 0 ? dargs.dims[0] : 1;
      int64_t total = 1;
      for (size_t i = 0; i < dargs.num_dims; ++i) total *= dargs.dims[i];
      int64_t cols = rows ? total / rows : total;
      *out_rows = rows;
      *out_cols = cols;
      if (total > out_capacity) {
        rc = -2;
      } else {
        PJRT_Buffer_ToHostBuffer_Args targs;
        memset(&targs, 0, sizeof(targs));
        targs.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
        targs.src = out_buf;
        targs.dst = out;
        targs.dst_size = static_cast<size_t>(total) * sizeof(float);
        if (!take_error(api, api->PJRT_Buffer_ToHostBuffer(&targs), "d2h") &&
            await_event(api, targs.event, "d2h event"))
          rc = 0;
      }
    }
  }
  for (auto* b : outputs) destroy_buffer(b);
  return rc;
}

}  // namespace

extern "C" {

// Single dense f32 input by name → first output, same convention as
// ptpu_infer/ptpu_aot_infer.
int ptpu_pjrt_infer(void* handle, const char* input_name, const float* data,
                    int64_t batch, int64_t dim, float* out,
                    int64_t out_capacity, int64_t* out_rows,
                    int64_t* out_cols) {
  return pjrt_infer_impl(static_cast<Model*>(handle), input_name, data, 0,
                         batch, dim, out, out_capacity, out_rows, out_cols);
}

// Single integer-id input ([batch] i32 — embedding models, .ptpj v2).
int ptpu_pjrt_infer_i32(void* handle, const char* input_name,
                        const int32_t* data, int64_t batch, float* out,
                        int64_t out_capacity, int64_t* out_rows,
                        int64_t* out_cols) {
  return pjrt_infer_impl(static_cast<Model*>(handle), input_name, data, 1,
                         batch, 1, out, out_capacity, out_rows, out_cols);
}

// Shared-param multi-instance serving (gradient_machine.h:88 analog):
// the compiled executable + its on-device weights are shared; PJRT
// execution is reentrant, so any number of threads may infer through any
// mix of handles. Freed on the last release, in any order.
void* ptpu_pjrt_create_shared(void* origin) {
  auto* m = static_cast<Model*>(origin);
  if (!m) return nullptr;
  m->refs.fetch_add(1, std::memory_order_relaxed);
  return m;
}

void ptpu_pjrt_release(void* handle) {
  auto* m = static_cast<Model*>(handle);
  if (m && m->refs.fetch_sub(1, std::memory_order_acq_rel) == 1)
    destroy_model(m);
}

}  // extern "C"
