// Native inference runner: load a .pdnative deploy artifact and execute it
// on any PJRT C-API plugin (libtpu.so, ...) — no Python.
//
// This is the TPU-native replacement for the reference's C++ inference
// entry (ref:paddle/fluid/inference/api/analysis_predictor.cc and the C API
// ref:paddle/fluid/inference/capi_exp/pd_inference_api.h): instead of a
// Program + C++ executor, the deploy unit is a single self-describing file
// holding StableHLO bytecode + serialized compile options + weights + I/O
// specs (written by paddle_tpu.jit.save). The runner dlopens a PJRT plugin,
// compiles the StableHLO once, uploads the weights once, and serves runs.
//
// C ABI (consumed by ctypes in paddle_tpu.inference.NativePredictor and by
// user C/C++ applications linking libpaddle_tpu_native.so):
//
//   PTInfer* pt_infer_create(plugin_so_path, artifact_path)
//   PTInfer* pt_infer_create_with_options(plugin_so_path, artifact_path,
//       "k=v;k=v")  // PJRT_Client_Create NamedValues; values may be
//       // type-tagged "i:<int>" / "s:<str>" (untagged: digits->int64).
//       // pt_infer_create reads PADDLE_TPU_PJRT_CREATE_OPTIONS instead.
//   const char* pt_infer_last_error()
//   int  pt_infer_input_count / pt_infer_output_count
//   int  pt_infer_input_spec / pt_infer_output_spec (dims/ndim/dtype out)
//   int  pt_infer_run(h, inputs[], n_in, outputs[], n_out)
//   void pt_infer_destroy(h)
//
// Artifact container (little-endian; writer: paddle_tpu/native/pdnative.py):
//   magic "PDNATIVE" | u32 version=1 | u32 nsections
//   section := u16 name_len | name | u64 data_len | data
//   sections: "platform", "compile_options", "stablehlo", "args", "outputs"
//   args    := u32 n | { u8 kind(0=weight,1=input) | u16 nlen | name |
//                        u8 dtype(PJRT_Buffer_Type) | u8 ndim | i64 dims[] |
//                        [kind==0: u64 nbytes | raw] }
//   outputs := u32 n | { u16 nlen | name | u8 dtype | u8 ndim | i64 dims[] }

#include <dlfcn.h>
#include <cctype>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <utility>
#include <vector>

#include "third_party/pjrt_c_api.h"

namespace {

thread_local std::string g_err;

void set_err(const std::string& m) { g_err = m; }

// ------------------------------------------------------------------ artifact

struct ArgSpec {
  bool is_weight = false;
  std::string name;
  int dtype = 0;  // PJRT_Buffer_Type
  std::vector<int64_t> dims;
  std::string data;  // weights only
  size_t nbytes() const {
    size_t n = dtype_size(dtype);
    for (int64_t d : dims) n *= static_cast<size_t>(d);
    return n;
  }
  static size_t dtype_size(int t) {
    switch (t) {
      case PJRT_Buffer_Type_PRED: case PJRT_Buffer_Type_S8:
      case PJRT_Buffer_Type_U8: return 1;
      case PJRT_Buffer_Type_S16: case PJRT_Buffer_Type_U16:
      case PJRT_Buffer_Type_F16: case PJRT_Buffer_Type_BF16: return 2;
      case PJRT_Buffer_Type_S32: case PJRT_Buffer_Type_U32:
      case PJRT_Buffer_Type_F32: return 4;
      case PJRT_Buffer_Type_S64: case PJRT_Buffer_Type_U64:
      case PJRT_Buffer_Type_F64: case PJRT_Buffer_Type_C64: return 8;
      case PJRT_Buffer_Type_C128: return 16;
      default: return 0;
    }
  }
};

struct Artifact {
  std::string platform;
  std::string compile_options;
  std::string stablehlo;
  std::vector<ArgSpec> args;     // in exported-main order (weights + inputs)
  std::vector<ArgSpec> outputs;  // dims/dtype only
};

class Reader {
 public:
  Reader(const char* p, size_t n) : p_(p), n_(n) {}
  // overflow-safe: k is attacker-controlled (u64 length fields in the file),
  // so `off_ + k` may wrap — compare against the remaining span instead
  bool bytes(void* out, size_t k) {
    if (k > n_ - off_) return false;
    memcpy(out, p_ + off_, k);
    off_ += k;
    return true;
  }
  bool str(std::string* out, size_t k) {
    if (k > n_ - off_) return false;
    out->assign(p_ + off_, k);
    off_ += k;
    return true;
  }
  template <typename T> bool num(T* v) { return bytes(v, sizeof(T)); }

 private:
  const char* p_;
  size_t n_, off_ = 0;
};

bool parse_specs(Reader& r, std::vector<ArgSpec>* out, bool with_kind) {
  uint32_t n;
  if (!r.num(&n)) return false;
  for (uint32_t i = 0; i < n; i++) {
    ArgSpec s;
    if (with_kind) {
      uint8_t kind;
      if (!r.num(&kind)) return false;
      s.is_weight = kind == 0;
    }
    uint16_t nlen;
    if (!r.num(&nlen) || !r.str(&s.name, nlen)) return false;
    uint8_t dt, nd;
    if (!r.num(&dt) || !r.num(&nd)) return false;
    s.dtype = dt;
    s.dims.resize(nd);
    for (uint8_t d = 0; d < nd; d++) {
      if (!r.num(&s.dims[d])) return false;
      if (s.dims[d] < 0) {
        set_err("artifact spec '" + s.name + "' has negative dim");
        return false;
      }
    }
    if (s.is_weight) {
      uint64_t nb;
      if (!r.num(&nb) || !r.str(&s.data, nb)) return false;
      if (nb != s.nbytes()) {
        set_err("artifact weight '" + s.name + "' size mismatch");
        return false;
      }
    }
    out->push_back(std::move(s));
  }
  return true;
}

bool load_artifact(const char* path, Artifact* a) {
  FILE* f = fopen(path, "rb");
  if (!f) {
    set_err(std::string("cannot open artifact: ") + path);
    return false;
  }
  fseek(f, 0, SEEK_END);
  long sz = ftell(f);
  fseek(f, 0, SEEK_SET);
  std::string buf(static_cast<size_t>(sz), '\0');
  size_t rd = fread(buf.data(), 1, buf.size(), f);
  fclose(f);
  if (rd != buf.size()) {
    set_err("short read on artifact");
    return false;
  }
  Reader r(buf.data(), buf.size());
  char magic[8];
  if (!r.bytes(magic, 8) || memcmp(magic, "PDNATIVE", 8) != 0) {
    set_err("bad artifact magic (not a .pdnative file)");
    return false;
  }
  uint32_t version, nsec;
  if (!r.num(&version) || version != 1) {
    set_err("unsupported .pdnative version");
    return false;
  }
  if (!r.num(&nsec)) return false;
  for (uint32_t i = 0; i < nsec; i++) {
    uint16_t nlen;
    std::string name, data;
    uint64_t dlen;
    if (!r.num(&nlen) || !r.str(&name, nlen) || !r.num(&dlen) ||
        !r.str(&data, dlen)) {
      set_err("truncated artifact section");
      return false;
    }
    if (name == "platform") {
      a->platform = data;
    } else if (name == "compile_options") {
      a->compile_options = data;
    } else if (name == "stablehlo") {
      a->stablehlo = data;
    } else if (name == "args") {
      Reader sr(data.data(), data.size());
      if (!parse_specs(sr, &a->args, /*with_kind=*/true)) return false;
    } else if (name == "outputs") {
      Reader sr(data.data(), data.size());
      if (!parse_specs(sr, &a->outputs, /*with_kind=*/false)) return false;
    }  // unknown sections: forward-compat skip
  }
  if (a->stablehlo.empty() || a->args.empty()) {
    set_err("artifact missing stablehlo/args sections");
    return false;
  }
  return true;
}

// ------------------------------------------------------------------- runner

struct PTInfer {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  Artifact art;
  std::vector<PJRT_Buffer*> weight_bufs;  // uploaded once, arg-order slots
  std::vector<int> input_arg_idx;         // position of each input in args
  size_t num_outputs = 0;
};

// Convert a PJRT_Error to g_err; destroys the error. True if there WAS one.
bool take_err(const PJRT_Api* api, PJRT_Error* e, const char* what) {
  if (e == nullptr) return false;
  PJRT_Error_Message_Args ma;
  memset(&ma, 0, sizeof(ma));
  ma.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  ma.error = e;
  api->PJRT_Error_Message(&ma);
  set_err(std::string(what) + ": " + std::string(ma.message, ma.message_size));
  PJRT_Error_Destroy_Args da;
  memset(&da, 0, sizeof(da));
  da.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  da.error = e;
  api->PJRT_Error_Destroy(&da);
  return true;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, const char* what) {
  if (ev == nullptr) return true;
  PJRT_Event_Await_Args aa;
  memset(&aa, 0, sizeof(aa));
  aa.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aa.event = ev;
  PJRT_Error* e = api->PJRT_Event_Await(&aa);
  PJRT_Event_Destroy_Args dd;
  memset(&dd, 0, sizeof(dd));
  dd.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dd.event = ev;
  api->PJRT_Event_Destroy(&dd);
  return !take_err(api, e, what);
}

void destroy_buffer(const PJRT_Api* api, PJRT_Buffer* b);

PJRT_Buffer* upload(PTInfer* h, const void* data, const ArgSpec& s,
                    const char* what) {
  PJRT_Client_BufferFromHostBuffer_Args ba;
  memset(&ba, 0, sizeof(ba));
  ba.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  ba.client = h->client;
  ba.data = data;
  ba.type = static_cast<PJRT_Buffer_Type>(s.dtype);
  ba.dims = s.dims.data();
  ba.num_dims = s.dims.size();
  ba.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  ba.device = h->device;
  if (take_err(h->api, h->api->PJRT_Client_BufferFromHostBuffer(&ba), what))
    return nullptr;
  if (!await_event(h->api, ba.done_with_host_buffer, what)) {
    destroy_buffer(h->api, ba.buffer);  // don't leak the device buffer
    return nullptr;
  }
  return ba.buffer;
}

void destroy_buffer(const PJRT_Api* api, PJRT_Buffer* b) {
  if (b == nullptr) return;
  PJRT_Buffer_Destroy_Args da;
  memset(&da, 0, sizeof(da));
  da.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  da.buffer = b;
  PJRT_Error* e = api->PJRT_Buffer_Destroy(&da);
  if (e != nullptr) take_err(api, e, "PJRT_Buffer_Destroy");
}

}  // namespace

extern "C" {

const char* pt_infer_last_error() { return g_err.c_str(); }

void pt_infer_destroy(PTInfer* h) {
  if (h == nullptr) return;
  if (h->api != nullptr) {
    for (PJRT_Buffer* b : h->weight_bufs) destroy_buffer(h->api, b);
    if (h->exec != nullptr) {
      PJRT_LoadedExecutable_Destroy_Args xa;
      memset(&xa, 0, sizeof(xa));
      xa.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      xa.executable = h->exec;
      h->api->PJRT_LoadedExecutable_Destroy(&xa);
    }
    if (h->client != nullptr) {
      PJRT_Client_Destroy_Args ca;
      memset(&ca, 0, sizeof(ca));
      ca.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      ca.client = h->client;
      h->api->PJRT_Client_Destroy(&ca);
    }
  }
  if (h->dl != nullptr) dlclose(h->dl);
  delete h;
}

PTInfer* pt_infer_create_with_options(const char* plugin_path,
                                      const char* artifact_path,
                                      const char* create_options);

PTInfer* pt_infer_create(const char* plugin_path, const char* artifact_path) {
  // back-compat / pure-C convenience: options come from the environment
  return pt_infer_create_with_options(
      plugin_path, artifact_path, getenv("PADDLE_TPU_PJRT_CREATE_OPTIONS"));
}

PTInfer* pt_infer_create_with_options(const char* plugin_path,
                                      const char* artifact_path,
                                      const char* create_options) {
  auto* h = new PTInfer();
  if (!load_artifact(artifact_path, &h->art)) {
    delete h;
    return nullptr;
  }
  h->dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (h->dl == nullptr) {
    set_err(std::string("dlopen failed: ") + dlerror());
    delete h;
    return nullptr;
  }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api =
      reinterpret_cast<GetPjrtApiFn>(dlsym(h->dl, "GetPjrtApi"));
  if (get_api == nullptr) {
    set_err("plugin has no GetPjrtApi symbol");
    pt_infer_destroy(h);
    return nullptr;
  }
  h->api = get_api();
  if (h->api == nullptr) {
    set_err("GetPjrtApi returned null");
    pt_infer_destroy(h);
    return nullptr;
  }

  PJRT_Plugin_Initialize_Args pa;
  memset(&pa, 0, sizeof(pa));
  pa.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (take_err(h->api, h->api->PJRT_Plugin_Initialize(&pa),
               "PJRT_Plugin_Initialize")) {
    pt_infer_destroy(h);
    return nullptr;
  }

  PJRT_Client_Create_Args cc;
  memset(&cc, 0, sizeof(cc));
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  // Plugin-specific client options from PADDLE_TPU_PJRT_CREATE_OPTIONS
  // ("k=v;k=v"; integer-looking values become kInt64, the rest kString).
  // Some plugins hard-require NamedValues at create time and reject a
  // bare create; libtpu needs none.
  std::vector<std::pair<std::string, std::string>> kvs;  // parsed pairs
  std::vector<PJRT_NamedValue> nvs;
  if (create_options != nullptr && create_options[0] != '\0') {
    std::string all(create_options);
    size_t pos = 0;
    while (pos < all.size()) {
      size_t semi = all.find(';', pos);
      if (semi == std::string::npos) semi = all.size();
      std::string pair = all.substr(pos, semi - pos);
      pos = semi + 1;
      size_t eq = pair.find('=');
      if (eq == std::string::npos || eq == 0) continue;
      kvs.emplace_back(pair.substr(0, eq), pair.substr(eq + 1));
    }
    // build after parsing: kvs is stable now, so the NamedValues' name /
    // string_value pointers stay valid through PJRT_Client_Create
    for (auto& kv : kvs) {
      const std::string& key = kv.first;
      std::string& val = kv.second;
      PJRT_NamedValue nv;
      memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = key.c_str();
      nv.name_size = key.size();
      // Values may carry an explicit type tag ("i:123" / "s:abc") — the
      // Python wrapper always emits tags so a digit-only STRING option is
      // never mis-typed. Untagged values (hand-written env) are guessed:
      // all-digits -> kInt64, else kString.
      bool forced_int = false, forced_str = false;
      if (val.size() >= 2 && val[1] == ':' &&
          (val[0] == 'i' || val[0] == 's')) {
        forced_int = val[0] == 'i';
        forced_str = val[0] == 's';
        val.erase(0, 2);
      }
      bool is_int = forced_int;
      if (!forced_int && !forced_str && !val.empty()) {
        is_int = true;
        for (size_t i = 0; i < val.size(); ++i) {
          if (!(isdigit(static_cast<unsigned char>(val[i])) ||
                (i == 0 && val[i] == '-' && val.size() > 1))) {
            is_int = false;
            break;
          }
        }
      }
      if (is_int) {
        errno = 0;
        char* endp = nullptr;
        long long parsed = strtoll(val.c_str(), &endp, 10);
        if (errno == ERANGE || endp == val.c_str() || *endp != '\0') {
          set_err("create option '" + key + "' has out-of-range or "
                  "non-integer value '" + val + "'");
          pt_infer_destroy(h);
          return nullptr;
        }
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = static_cast<int64_t>(parsed);
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = val.c_str();
        nv.value_size = val.size();
      }
      nvs.push_back(nv);
    }
    cc.create_options = nvs.data();
    cc.num_options = nvs.size();
  }
  if (take_err(h->api, h->api->PJRT_Client_Create(&cc), "PJRT_Client_Create")) {
    pt_infer_destroy(h);
    return nullptr;
  }
  h->client = cc.client;

  PJRT_Client_AddressableDevices_Args da;
  memset(&da, 0, sizeof(da));
  da.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  da.client = h->client;
  if (take_err(h->api, h->api->PJRT_Client_AddressableDevices(&da),
               "PJRT_Client_AddressableDevices") ||
      da.num_addressable_devices == 0) {
    if (g_err.empty()) set_err("plugin reports no addressable devices");
    pt_infer_destroy(h);
    return nullptr;
  }
  h->device = da.addressable_devices[0];

  PJRT_Program prog;
  memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = h->art.stablehlo.data();
  prog.code_size = h->art.stablehlo.size();
  static const char kFormat[] = "mlir";
  prog.format = kFormat;
  prog.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args co;
  memset(&co, 0, sizeof(co));
  co.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  co.client = h->client;
  co.program = &prog;
  co.compile_options = h->art.compile_options.data();
  co.compile_options_size = h->art.compile_options.size();
  if (take_err(h->api, h->api->PJRT_Client_Compile(&co),
               "PJRT_Client_Compile")) {
    pt_infer_destroy(h);
    return nullptr;
  }
  h->exec = co.executable;

  // cross-check output arity with the plugin's view of the executable
  PJRT_LoadedExecutable_GetExecutable_Args ge;
  memset(&ge, 0, sizeof(ge));
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.loaded_executable = h->exec;
  if (!take_err(h->api, h->api->PJRT_LoadedExecutable_GetExecutable(&ge),
                "PJRT_LoadedExecutable_GetExecutable")) {
    PJRT_Executable_NumOutputs_Args no;
    memset(&no, 0, sizeof(no));
    no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    no.executable = ge.executable;
    if (!take_err(h->api, h->api->PJRT_Executable_NumOutputs(&no),
                  "PJRT_Executable_NumOutputs"))
      h->num_outputs = no.num_outputs;
  }
  if (h->num_outputs == 0) h->num_outputs = h->art.outputs.size();
  if (!h->art.outputs.empty() && h->num_outputs != h->art.outputs.size()) {
    set_err("plugin/artifact output count mismatch");
    pt_infer_destroy(h);
    return nullptr;
  }

  // upload weights once; record where runtime inputs slot into the arg list
  h->weight_bufs.assign(h->art.args.size(), nullptr);
  for (size_t i = 0; i < h->art.args.size(); i++) {
    const ArgSpec& s = h->art.args[i];
    if (s.is_weight) {
      h->weight_bufs[i] = upload(h, s.data.data(), s, "weight upload");
      if (h->weight_bufs[i] == nullptr) {
        pt_infer_destroy(h);
        return nullptr;
      }
    } else {
      h->input_arg_idx.push_back(static_cast<int>(i));
    }
  }
  return h;
}

int pt_infer_input_count(PTInfer* h) {
  return static_cast<int>(h->input_arg_idx.size());
}

int pt_infer_output_count(PTInfer* h) {
  return static_cast<int>(h->num_outputs);
}

static int spec_out(const ArgSpec& s, int64_t* dims, int* ndim, int* dtype) {
  if (static_cast<size_t>(*ndim) < s.dims.size()) {
    set_err("dims buffer too small: need " + std::to_string(s.dims.size()));
    return -1;
  }
  *ndim = static_cast<int>(s.dims.size());
  for (size_t d = 0; d < s.dims.size(); d++) dims[d] = s.dims[d];
  *dtype = s.dtype;
  return 0;
}

int pt_infer_input_spec(PTInfer* h, int i, int64_t* dims, int* ndim,
                        int* dtype) {
  if (i < 0 || i >= pt_infer_input_count(h)) {
    set_err("input index out of range");
    return -1;
  }
  return spec_out(h->art.args[h->input_arg_idx[i]], dims, ndim, dtype);
}

int pt_infer_output_spec(PTInfer* h, int i, int64_t* dims, int* ndim,
                         int* dtype) {
  if (i < 0 || static_cast<size_t>(i) >= h->art.outputs.size()) {
    set_err("output index out of range");
    return -1;
  }
  return spec_out(h->art.outputs[i], dims, ndim, dtype);
}

// inputs: host pointers, one per runtime input (artifact order, dense
// major-to-minor). outputs: preallocated host buffers sized per output spec.
int pt_infer_run(PTInfer* h, const void** inputs, int n_inputs, void** outputs,
                 int n_outputs) {
  if (n_inputs != pt_infer_input_count(h)) {
    set_err("wrong number of inputs");
    return -1;
  }
  if (n_outputs != pt_infer_output_count(h)) {
    set_err("wrong number of outputs");
    return -1;
  }
  std::vector<PJRT_Buffer*> arglist(h->weight_bufs);
  std::vector<PJRT_Buffer*> to_free;
  bool ok = true;
  for (int i = 0; i < n_inputs && ok; i++) {
    int slot = h->input_arg_idx[i];
    PJRT_Buffer* b = upload(h, inputs[i], h->art.args[slot], "input upload");
    if (b == nullptr) {
      ok = false;
      break;
    }
    arglist[slot] = b;
    to_free.push_back(b);
  }

  std::vector<PJRT_Buffer*> outbufs(h->num_outputs, nullptr);
  if (ok) {
    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    PJRT_Buffer* const* arg_lists[1] = {arglist.data()};
    PJRT_Buffer** out_lists[1] = {outbufs.data()};
    PJRT_Event* done[1] = {nullptr};

    PJRT_LoadedExecutable_Execute_Args ex;
    memset(&ex, 0, sizeof(ex));
    ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ex.executable = h->exec;
    ex.options = &opts;
    ex.argument_lists = arg_lists;
    ex.num_devices = 1;
    ex.num_args = arglist.size();
    ex.output_lists = out_lists;
    ex.device_complete_events = done;
    ok = !take_err(h->api, h->api->PJRT_LoadedExecutable_Execute(&ex),
                   "PJRT_LoadedExecutable_Execute");
    if (ok) ok = await_event(h->api, done[0], "execute completion");
  }

  for (size_t i = 0; i < h->num_outputs && ok; i++) {
    PJRT_Buffer_ToHostBuffer_Args th;
    memset(&th, 0, sizeof(th));
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = outbufs[i];
    th.dst = nullptr;  // query size first: artifact spec may disagree
    ok = !take_err(h->api, h->api->PJRT_Buffer_ToHostBuffer(&th),
                   "PJRT_Buffer_ToHostBuffer(size)");
    if (!ok) break;
    size_t need = th.dst_size;
    if (i < h->art.outputs.size() && need != h->art.outputs[i].nbytes()) {
      set_err("output " + std::to_string(i) + " size mismatch: device says " +
              std::to_string(need) + " bytes, artifact spec says " +
              std::to_string(h->art.outputs[i].nbytes()));
      ok = false;
      break;
    }
    memset(&th, 0, sizeof(th));
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = outbufs[i];
    th.dst = outputs[i];
    th.dst_size = need;
    ok = !take_err(h->api, h->api->PJRT_Buffer_ToHostBuffer(&th),
                   "PJRT_Buffer_ToHostBuffer");
    if (ok) ok = await_event(h->api, th.event, "host transfer");
  }

  for (PJRT_Buffer* b : outbufs) destroy_buffer(h->api, b);
  for (PJRT_Buffer* b : to_free) destroy_buffer(h->api, b);
  return ok ? 0 : -1;
}

}  // extern "C"
