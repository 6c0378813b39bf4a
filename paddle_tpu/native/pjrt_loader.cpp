// C++ inference runtime: loads a StableHLO artifact produced by
// paddle_tpu.jit.save and executes it on any PJRT plugin (TPU via
// libtpu, or any other PJRT .so).
//
// Reference parity: the C++ deployment pair — paddle/fluid/jit/ (C++
// loader for jit.save'd functions) and the AnalysisPredictor C++ API
// (paddle/fluid/inference/) — upstream locations unverified, see
// SURVEY.md §2.1 "C++ JIT" / "Inference engine".
//
// TPU-native design: the portable program format is StableHLO bytecode
// (what jax.export produces) and the portable runtime ABI is the PJRT C
// API — the same plugin interface JAX itself sits on. This file is a
// dependency-free PJRT C-API client (~no XLA build needed): dlopen the
// plugin, GetPjrtApi(), compile the module, move host buffers in, run,
// move results out. Exposed two ways:
//   - C ABI (pd_pjrt_*) consumed by ctypes (paddle_tpu.native.PjrtRunner)
//   - a CLI (build with -DPD_PJRT_MAIN) for pure-C++ deployment:
//       pd_infer <plugin.so> <artifact_prefix> [out_dir [in0.bin ...]]
//
// Compile options: PJRT_Client_Compile wants a serialized
// xla.CompileOptionsProto. We hand-encode the minimal message
// (num_replicas=1, num_partitions=1) with a 10-line protobuf writer
// rather than pulling in protobuf — the schema is stable and tiny.

#include <dlfcn.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include <memory>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct Ctx {
  void* dso = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;  // first addressable device
  std::string last_error;
};

struct Exec {
  Ctx* ctx = nullptr;
  PJRT_LoadedExecutable* le = nullptr;
  size_t num_outputs = 0;
};

struct Result {
  Ctx* ctx = nullptr;
  std::vector<PJRT_Buffer*> bufs;
};

std::string take_error(const PJRT_Api* api, PJRT_Error* err) {
  if (!err) return "";
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return msg;
}

#define CHECK_PJRT(ctx, call)                      \
  do {                                             \
    PJRT_Error* _e = (call);                       \
    if (_e) {                                      \
      (ctx)->last_error = take_error((ctx)->api, _e); \
      return nullptr;                              \
    }                                              \
  } while (0)

bool await_event(Ctx* c, PJRT_Event* ev) {
  if (!ev) return true;
  PJRT_Event_Await_Args aargs;
  memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = ev;
  PJRT_Error* err = c->api->PJRT_Event_Await(&aargs);
  if (err) c->last_error = take_error(c->api, err);
  PJRT_Event_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  c->api->PJRT_Event_Destroy(&dargs);
  return !err;
}

// -- minimal protobuf writer for xla.CompileOptionsProto ---------------------
// Field numbers verified against jax's own CompileOptions serialization
// (decoded in-session): CompileOptionsProto.executable_build_options is
// field 3; ExecutableBuildOptionsProto.num_replicas/num_partitions are
// fields 4/5 (varint).
void pb_varint(std::string& s, uint64_t v) {
  while (v >= 0x80) { s.push_back(char(v | 0x80)); v >>= 7; }
  s.push_back(char(v));
}
void pb_tag(std::string& s, int field, int wire) {
  pb_varint(s, uint64_t(field) << 3 | wire);
}
std::string compile_options_proto() {
  std::string ebo;
  pb_tag(ebo, 4, 0); pb_varint(ebo, 1);  // num_replicas = 1
  pb_tag(ebo, 5, 0); pb_varint(ebo, 1);  // num_partitions = 1
  std::string co;
  pb_tag(co, 3, 2);  // executable_build_options, length-delimited
  pb_varint(co, ebo.size());
  co += ebo;
  return co;
}

PJRT_Buffer_Type dtype_code(int code) {
  switch (code) {
    case 0: return PJRT_Buffer_Type_F32;
    case 1: return PJRT_Buffer_Type_BF16;
    case 2: return PJRT_Buffer_Type_S32;
    case 3: return PJRT_Buffer_Type_F16;
    case 4: return PJRT_Buffer_Type_F64;
    case 5: return PJRT_Buffer_Type_S64;
    case 6: return PJRT_Buffer_Type_PRED;
    case 7: return PJRT_Buffer_Type_S8;
    case 8: return PJRT_Buffer_Type_U8;
    default: return PJRT_Buffer_Type_INVALID;
  }
}

}  // namespace

namespace {

// "k=v;k=v" option string → NamedValues. Values of all digits become
// kInt64, everything else kString (matches what plugins expect from
// jax's register_plugin options dict).
struct ParsedOptions {
  std::vector<std::string> keys, svals;
  std::vector<int64_t> ivals;
  std::vector<bool> is_int;
  std::vector<PJRT_NamedValue> nv;

  explicit ParsedOptions(const char* spec) {
    if (!spec) return;
    std::string s(spec);
    size_t pos = 0;
    while (pos < s.size()) {
      size_t semi = s.find(';', pos);
      if (semi == std::string::npos) semi = s.size();
      std::string kv = s.substr(pos, semi - pos);
      pos = semi + 1;
      size_t eq = kv.find('=');
      if (eq == std::string::npos) continue;
      keys.push_back(kv.substr(0, eq));
      std::string v = kv.substr(eq + 1);
      bool digits = !v.empty();
      for (size_t ci = 0; ci < v.size(); ++ci) {
        char ch = v[ci];
        if (!(ch >= '0' && ch <= '9') && !(ch == '-' && ci == 0))
          digits = false;
      }
      if (v == "-") digits = false;
      is_int.push_back(digits);
      svals.push_back(v);
      ivals.push_back(digits ? strtoll(v.c_str(), nullptr, 10) : 0);
    }
    nv.resize(keys.size());
    for (size_t i = 0; i < keys.size(); ++i) {
      memset(&nv[i], 0, sizeof(nv[i]));
      nv[i].struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv[i].name = keys[i].c_str();
      nv[i].name_size = keys[i].size();
      if (is_int[i]) {
        nv[i].type = PJRT_NamedValue_kInt64;
        nv[i].int64_value = ivals[i];
        nv[i].value_size = 1;
      } else {
        nv[i].type = PJRT_NamedValue_kString;
        nv[i].string_value = svals[i].c_str();
        nv[i].value_size = svals[i].size();
      }
    }
  }
};

}  // namespace

extern "C" {

// -- lifecycle ---------------------------------------------------------------

// options: "key=value;key=value" (int-looking values become kInt64).
// nullptr/"" = no options (libtpu needs none).
void* pd_pjrt_create(const char* plugin_path, const char* options) {
  auto* c = new Ctx();
  ParsedOptions popts(options);
  c->dso = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!c->dso) {
    fprintf(stderr, "pd_pjrt: dlopen(%s): %s\n", plugin_path, dlerror());
    delete c;
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(c->dso, "GetPjrtApi"));
  if (!get_api) {
    fprintf(stderr, "pd_pjrt: no GetPjrtApi in %s\n", plugin_path);
    dlclose(c->dso);
    delete c;
    return nullptr;
  }
  c->api = get_api();

  PJRT_Plugin_Initialize_Args iargs;
  memset(&iargs, 0, sizeof(iargs));
  iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (PJRT_Error* e = c->api->PJRT_Plugin_Initialize(&iargs)) {
    fprintf(stderr, "pd_pjrt: plugin init: %s\n",
            take_error(c->api, e).c_str());
    delete c;
    return nullptr;
  }

  PJRT_Client_Create_Args cargs;
  memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = popts.nv.empty() ? nullptr : popts.nv.data();
  cargs.num_options = popts.nv.size();
  if (PJRT_Error* e = c->api->PJRT_Client_Create(&cargs)) {
    fprintf(stderr, "pd_pjrt: client create: %s\n",
            take_error(c->api, e).c_str());
    delete c;
    return nullptr;
  }
  c->client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = c->client;
  if (PJRT_Error* e = c->api->PJRT_Client_AddressableDevices(&dargs)) {
    fprintf(stderr, "pd_pjrt: devices: %s\n", take_error(c->api, e).c_str());
    delete c;
    return nullptr;
  }
  if (dargs.num_addressable_devices == 0) {
    fprintf(stderr, "pd_pjrt: no addressable devices\n");
    delete c;
    return nullptr;
  }
  c->device = dargs.addressable_devices[0];
  return c;
}

const char* pd_pjrt_last_error(void* ctx) {
  return static_cast<Ctx*>(ctx)->last_error.c_str();
}

void pd_pjrt_destroy(void* ctx) {
  auto* c = static_cast<Ctx*>(ctx);
  if (c->client) {
    PJRT_Client_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = c->client;
    c->api->PJRT_Client_Destroy(&args);
  }
  // NOTE: not dlclosing — TPU plugins register global state.
  delete c;
}

// -- compile ------------------------------------------------------------------

void* pd_pjrt_compile(void* ctx, const char* code, size_t code_size) {
  auto* c = static_cast<Ctx*>(ctx);
  PJRT_Program prog;
  memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char*>(code);
  prog.code_size = code_size;
  static const char kFormat[] = "mlir";
  prog.format = kFormat;
  prog.format_size = sizeof(kFormat) - 1;

  std::string opts = compile_options_proto();
  PJRT_Client_Compile_Args cargs;
  memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  cargs.client = c->client;
  cargs.program = &prog;
  cargs.compile_options = opts.data();
  cargs.compile_options_size = opts.size();
  CHECK_PJRT(c, c->api->PJRT_Client_Compile(&cargs));

  auto* e = new Exec();
  e->ctx = c;
  e->le = cargs.executable;

  // number of outputs, via the underlying PJRT_Executable
  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = e->le;
  if (PJRT_Error* err = c->api->PJRT_LoadedExecutable_GetExecutable(&gargs)) {
    c->last_error = take_error(c->api, err);
    delete e;
    return nullptr;
  }
  PJRT_Executable_NumOutputs_Args nargs;
  memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  if (PJRT_Error* err = c->api->PJRT_Executable_NumOutputs(&nargs)) {
    c->last_error = take_error(c->api, err);
    delete e;
    return nullptr;
  }
  e->num_outputs = nargs.num_outputs;
  return e;
}

size_t pd_pjrt_num_outputs(void* exec) {
  return static_cast<Exec*>(exec)->num_outputs;
}

// -- execute ------------------------------------------------------------------

// dtypes: per-arg code (see dtype_code); dims_flat: concatenated dims,
// ranks[i] entries each; data: host pointers (dense, major-to-minor).
void* pd_pjrt_execute(void* exec, size_t n_args, const int* dtypes,
                      const int* ranks, const int64_t* dims_flat,
                      const void* const* data) {
  auto* e = static_cast<Exec*>(exec);
  Ctx* c = e->ctx;

  std::vector<PJRT_Buffer*> in_bufs(n_args, nullptr);
  size_t off = 0;
  for (size_t i = 0; i < n_args; ++i) {
    PJRT_Client_BufferFromHostBuffer_Args bargs;
    memset(&bargs, 0, sizeof(bargs));
    bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bargs.client = c->client;
    bargs.data = data[i];
    bargs.type = dtype_code(dtypes[i]);
    bargs.dims = dims_flat + off;
    bargs.num_dims = size_t(ranks[i]);
    off += size_t(ranks[i]);
    bargs.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableOnlyDuringCall;
    bargs.device = c->device;
    if (PJRT_Error* err = c->api->PJRT_Client_BufferFromHostBuffer(&bargs)) {
      c->last_error = take_error(c->api, err);
      return nullptr;
    }
    if (!await_event(c, bargs.done_with_host_buffer)) return nullptr;
    in_bufs[i] = bargs.buffer;
  }

  PJRT_ExecuteOptions opts;
  memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  std::vector<PJRT_Buffer*> outs(e->num_outputs, nullptr);
  PJRT_Buffer* const* arg_list = in_bufs.data();
  PJRT_Buffer** out_list = outs.data();
  PJRT_Event* done = nullptr;

  PJRT_LoadedExecutable_Execute_Args xargs;
  memset(&xargs, 0, sizeof(xargs));
  xargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  xargs.executable = e->le;
  xargs.options = &opts;
  xargs.argument_lists = &arg_list;
  xargs.num_devices = 1;
  xargs.num_args = n_args;
  xargs.output_lists = &out_list;
  xargs.device_complete_events = &done;
  PJRT_Error* err = c->api->PJRT_LoadedExecutable_Execute(&xargs);
  if (err) {
    c->last_error = take_error(c->api, err);
    return nullptr;
  }
  if (!await_event(c, done)) return nullptr;

  for (PJRT_Buffer* b : in_bufs) {
    PJRT_Buffer_Destroy_Args dargs;
    memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    dargs.buffer = b;
    c->api->PJRT_Buffer_Destroy(&dargs);
  }

  auto* r = new Result();
  r->ctx = c;
  r->bufs = std::move(outs);
  return r;
}

int64_t pd_pjrt_output_size(void* result, size_t i) {
  auto* r = static_cast<Result*>(result);
  Ctx* c = r->ctx;
  PJRT_Buffer_ToHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = r->bufs[i];
  args.dst = nullptr;  // size query
  if (PJRT_Error* err = c->api->PJRT_Buffer_ToHostBuffer(&args)) {
    c->last_error = take_error(c->api, err);
    return -1;
  }
  return int64_t(args.dst_size);
}

int pd_pjrt_output_copy(void* result, size_t i, void* dst, size_t dst_size) {
  auto* r = static_cast<Result*>(result);
  Ctx* c = r->ctx;
  PJRT_Buffer_ToHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = r->bufs[i];
  args.dst = dst;
  args.dst_size = dst_size;
  if (PJRT_Error* err = c->api->PJRT_Buffer_ToHostBuffer(&args)) {
    c->last_error = take_error(c->api, err);
    return -1;
  }
  return await_event(c, args.event) ? 0 : -1;
}

void pd_pjrt_result_destroy(void* result) {
  auto* r = static_cast<Result*>(result);
  for (PJRT_Buffer* b : r->bufs) {
    PJRT_Buffer_Destroy_Args dargs;
    memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    dargs.buffer = b;
    r->ctx->api->PJRT_Buffer_Destroy(&dargs);
  }
  delete r;
}

void pd_pjrt_exec_destroy(void* exec) {
  auto* e = static_cast<Exec*>(exec);
  PJRT_LoadedExecutable_Destroy_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  args.executable = e->le;
  e->ctx->api->PJRT_LoadedExecutable_Destroy(&args);
  delete e;
}

}  // extern "C"

// -- CLI ----------------------------------------------------------------------
// pd_infer <plugin.so> <artifact_prefix> [out_dir]
// Reads <prefix>.mlir (StableHLO bytecode), <prefix>.pdpjrt.txt (arg
// manifest) and <prefix>.pdparams.bin (param blob); writes out_<i>.bin.
#ifdef PD_PJRT_MAIN

static std::string read_file(const std::string& p) {
  FILE* f = fopen(p.c_str(), "rb");
  if (!f) { fprintf(stderr, "pd_infer: cannot open %s\n", p.c_str()); exit(2); }
  fseek(f, 0, SEEK_END);
  long n = ftell(f);
  fseek(f, 0, SEEK_SET);
  std::string s(size_t(n), '\0');
  if (fread(s.data(), 1, size_t(n), f) != size_t(n)) exit(2);
  fclose(f);
  return s;
}

int main(int argc, char** argv) {
  if (argc < 3) {
    fprintf(stderr,
            "usage: pd_infer <plugin.so> <artifact_prefix> [out_dir]\n");
    return 2;
  }
  std::string prefix = argv[2];
  std::string out_dir = argc > 3 ? argv[3] : ".";
  std::string code = read_file(prefix + ".mlir");
  std::string params = read_file(prefix + ".pdparams.bin");
  std::string manifest = read_file(prefix + ".pdpjrt.txt");

  // manifest lines: "arg <dtype_code> <rank> <d0> ... <param|input> <offset>"
  std::vector<int> dtypes, ranks;
  std::vector<int64_t> dims;
  std::vector<const void*> data;
  std::vector<std::string> input_files;
  char* save = nullptr;
  std::string m = manifest;
  for (char* line = strtok_r(m.data(), "\n", &save); line;
       line = strtok_r(nullptr, "\n", &save)) {
    char kind[16], src[16];
    int dt, rank;
    int consumed;
    if (sscanf(line, "%15s %d %d%n", kind, &dt, &rank, &consumed) != 3)
      continue;
    if (strcmp(kind, "arg") != 0) continue;
    dtypes.push_back(dt);
    ranks.push_back(rank);
    const char* p = line + consumed;
    for (int d = 0; d < rank; ++d) {
      long long v;
      int used;
      sscanf(p, " %lld%n", &v, &used);
      dims.push_back(v);
      p += used;
    }
    long long off;
    sscanf(p, " %15s %lld", src, &off);
    if (strcmp(src, "param") == 0) {
      data.push_back(params.data() + off);
    } else {
      data.push_back(nullptr);  // filled from input files below
      input_files.push_back("");
    }
  }
  // remaining argv entries are input .bin files, in manifest order
  size_t next_in = 0;
  std::vector<std::string> in_blobs;
  for (size_t i = 0; i < data.size(); ++i) {
    if (data[i] != nullptr) continue;
    int ai = 4 + int(next_in);  // argv: 0 prog, 1 plugin, 2 prefix, 3 outdir
    if (ai >= argc) {
      fprintf(stderr, "pd_infer: missing input file %zu\n", next_in);
      return 2;
    }
    in_blobs.push_back(read_file(argv[ai]));
    ++next_in;
  }
  next_in = 0;
  for (size_t i = 0; i < data.size(); ++i)
    if (data[i] == nullptr) data[i] = in_blobs[next_in++].data();

  // plugin options from PD_PJRT_OPTIONS ("k=v;k=v")
  void* ctx = pd_pjrt_create(argv[1], getenv("PD_PJRT_OPTIONS"));
  if (!ctx) return 1;
  void* exec = pd_pjrt_compile(ctx, code.data(), code.size());
  if (!exec) {
    fprintf(stderr, "pd_infer: compile: %s\n", pd_pjrt_last_error(ctx));
    return 1;
  }
  void* res = pd_pjrt_execute(exec, data.size(), dtypes.data(), ranks.data(),
                              dims.data(), data.data());
  if (!res) {
    fprintf(stderr, "pd_infer: execute: %s\n", pd_pjrt_last_error(ctx));
    return 1;
  }
  size_t nout = pd_pjrt_num_outputs(exec);
  for (size_t i = 0; i < nout; ++i) {
    int64_t sz = pd_pjrt_output_size(res, i);
    if (sz < 0) return 1;
    std::string buf(size_t(sz), '\0');
    if (pd_pjrt_output_copy(res, i, buf.data(), size_t(sz)) != 0) return 1;
    std::string path = out_dir + "/out_" + std::to_string(i) + ".bin";
    FILE* f = fopen(path.c_str(), "wb");
    fwrite(buf.data(), 1, buf.size(), f);
    fclose(f);
    printf("out_%zu %lld bytes -> %s\n", i, (long long)sz, path.c_str());
  }
  pd_pjrt_result_destroy(res);
  pd_pjrt_exec_destroy(exec);
  pd_pjrt_destroy(ctx);
  return 0;
}
#endif  // PD_PJRT_MAIN
