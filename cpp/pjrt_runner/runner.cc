// Native C++ inference runner over the PJRT C API.
//
// The TPU-native equivalent of the reference's PytorchToCpp libtorch app
// (/root/reference/.gitmodules:4-6, README.md:65-79): loads the StableHLO
// module exported by `real_time_helmet_detection_tpu.export` (the fused
// network->decode->NMS program with weights baked in, = the TorchScript
// trace) into any PJRT plugin (a TPU plugin such as libtpu.so, or a CPU
// plugin) and runs timed inference, printing detections and FPS.
//
// Usage:
//   pjrt_runner <plugin.so> <export_dir> [--image raw_f32_file] [--iters N]
//               [--depth D] [--opt key=value]...
//
// --depth D (default 1) keeps up to D frames in flight: frame i+1 is
// dispatched before frame i's detections are fetched, so D2H and host
// consumption overlap device execution — the deployment analogue of the
// Python side's software-pipelined eval loop. Depth 1 is the strictly
// sequential mode whose per-frame time is an honest latency measure.
//
// --opt passes PJRT_NamedValue client-create options (repeatable). Values
// parse as int64 when they look like integers, else as strings — whatever
// the plugin documents, e.g.:
//   --opt topology=v5e:1x1x1 --opt n_slices=1
//
// <export_dir> must contain exported_predict.stablehlo.mlir, meta.json and
// compile_options.pb as written by export_predict(). The optional image file
// is raw float32 NHWC bytes matching meta.json's input_shape (the Python
// side writes one with utils.imload + ndarray.tofile); without it a zero
// image is used (timing is input-independent).

#include <dlfcn.h>

#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

[[noreturn]] void Die(const std::string& msg) {
  std::fprintf(stderr, "pjrt_runner: %s\n", msg.c_str());
  std::exit(1);
}

std::string ReadFile(const std::string& path, bool binary = true) {
  std::ifstream f(path, binary ? std::ios::binary : std::ios::in);
  if (!f) Die("cannot open " + path);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

const PJRT_Api* g_api = nullptr;

// Test-only (--no-host-layout 1): omit the explicit row-major host_layout
// request so CI can prove the stub plugin catches the device-layout bug
// class the r2 hardware run exposed (tests/test_pjrt_runner.py).
bool g_no_host_layout = false;

void Check(PJRT_Error* err, const char* what) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  g_api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  g_api->PJRT_Error_Destroy(&dargs);
  Die(std::string(what) + ": " + msg);
}

void Await(PJRT_Event* event, const char* what) {
  PJRT_Event_Await_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  args.event = event;
  Check(g_api->PJRT_Event_Await(&args), what);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = event;
  Check(g_api->PJRT_Event_Destroy(&dargs), "event destroy");
}

// Minimal JSON number-array / scalar extraction (meta.json is machine
// written; a full JSON parser would be dead weight here).
std::vector<long> JsonIntArray(const std::string& json, const std::string& key) {
  auto pos = json.find("\"" + key + "\"");
  if (pos == std::string::npos) Die("meta.json missing key " + key);
  auto lb = json.find('[', pos);
  auto rb = json.find(']', lb);
  std::vector<long> out;
  std::string body = json.substr(lb + 1, rb - lb - 1);
  std::stringstream ss(body);
  std::string tok;
  while (std::getline(ss, tok, ',')) out.push_back(std::stol(tok));
  return out;
}

std::string JsonString(const std::string& json, const std::string& key,
                       const std::string& fallback) {
  auto pos = json.find("\"" + key + "\"");
  if (pos == std::string::npos) return fallback;
  auto colon = json.find(':', pos);
  auto q1 = json.find('"', colon);
  auto q2 = json.find('"', q1 + 1);
  return json.substr(q1 + 1, q2 - q1 - 1);
}

struct HostOutput {
  std::vector<char> bytes;
  std::vector<int64_t> dims;
};

HostOutput BufferToHost(PJRT_Buffer* buf) {
  HostOutput out;
  PJRT_Buffer_Dimensions_Args dim_args;
  std::memset(&dim_args, 0, sizeof(dim_args));
  dim_args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  dim_args.buffer = buf;
  Check(g_api->PJRT_Buffer_Dimensions(&dim_args), "buffer dims");
  out.dims.assign(dim_args.dims, dim_args.dims + dim_args.num_dims);

  // Request a dense row-major host layout explicitly: with host_layout
  // omitted the copy arrives in the buffer's DEVICE layout, and on TPU a
  // (B, N, 4) f32 array comes back transposed/tiled (observed: box
  // coordinates interleaved across detections).
  std::vector<int64_t> minor_to_major(out.dims.size());
  for (size_t i = 0; i < minor_to_major.size(); ++i)
    minor_to_major[i] = static_cast<int64_t>(minor_to_major.size() - 1 - i);
  PJRT_Buffer_MemoryLayout layout;
  std::memset(&layout, 0, sizeof(layout));
  layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  layout.tiled.minor_to_major = minor_to_major.data();
  layout.tiled.minor_to_major_size = minor_to_major.size();

  PJRT_Buffer_ToHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = buf;
  args.host_layout = g_no_host_layout ? nullptr : &layout;
  Check(g_api->PJRT_Buffer_ToHostBuffer(&args), "query host size");
  out.bytes.resize(args.dst_size);
  args.dst = out.bytes.data();
  Check(g_api->PJRT_Buffer_ToHostBuffer(&args), "copy to host");
  Await(args.event, "copy event");
  return out;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 3) {
    std::fprintf(stderr,
                 "usage: %s <plugin.so> <export_dir> [--image f32.raw] "
                 "[--iters N] [--depth D]\n", argv[0]);
    return 2;
  }
  const std::string plugin_path = argv[1];
  const std::string export_dir = argv[2];
  std::string image_path;
  int iters = 20;
  int depth = 1;
  std::vector<std::pair<std::string, std::string>> create_opts;
  for (int i = 3; i + 1 < argc; i += 2) {
    if (!std::strcmp(argv[i], "--image")) image_path = argv[i + 1];
    else if (!std::strcmp(argv[i], "--iters")) iters = std::atoi(argv[i + 1]);
    else if (!std::strcmp(argv[i], "--depth")) depth = std::atoi(argv[i + 1]);
    else if (!std::strcmp(argv[i], "--no-host-layout"))
      g_no_host_layout = std::atoi(argv[i + 1]) != 0;
    else if (!std::strcmp(argv[i], "--opt")) {
      std::string kv = argv[i + 1];
      auto eq = kv.find('=');
      if (eq == std::string::npos) Die("--opt needs key=value: " + kv);
      create_opts.emplace_back(kv.substr(0, eq), kv.substr(eq + 1));
    }
  }

  // --- plugin ---------------------------------------------------------------
  void* handle = dlopen(plugin_path.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (!handle) Die(std::string("dlopen failed: ") + dlerror());
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetPjrtApiFn>(dlsym(handle, "GetPjrtApi"));
  if (!get_api) Die("plugin has no GetPjrtApi symbol");
  g_api = get_api();
  if (!g_api) Die("GetPjrtApi returned null");
  std::printf("plugin %s: PJRT API v%d.%d\n", plugin_path.c_str(),
              g_api->pjrt_api_version.major_version,
              g_api->pjrt_api_version.minor_version);

  // --- client + device ------------------------------------------------------
  std::vector<PJRT_NamedValue> named;
  std::vector<int64_t> int_storage(create_opts.size());
  for (size_t i = 0; i < create_opts.size(); ++i) {
    const auto& [key, val] = create_opts[i];
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = key.c_str();
    nv.name_size = key.size();
    char* end = nullptr;
    long long iv = std::strtoll(val.c_str(), &end, 10);
    if (!val.empty() && end && *end == '\0') {
      nv.type = PJRT_NamedValue_kInt64;
      int_storage[i] = iv;
      nv.int64_value = int_storage[i];
      nv.value_size = 1;
    } else {
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = val.c_str();
      nv.value_size = val.size();
    }
    named.push_back(nv);
  }

  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = named.empty() ? nullptr : named.data();
  cargs.num_options = named.size();
  Check(g_api->PJRT_Client_Create(&cargs), "client create");
  PJRT_Client* client = cargs.client;

  PJRT_Client_AddressableDevices_Args devargs;
  std::memset(&devargs, 0, sizeof(devargs));
  devargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  devargs.client = client;
  Check(g_api->PJRT_Client_AddressableDevices(&devargs), "devices");
  if (devargs.num_addressable_devices == 0) Die("no addressable devices");
  PJRT_Device* device = devargs.addressable_devices[0];
  std::printf("devices: %zu (using device 0)\n",
              devargs.num_addressable_devices);

  // --- compile --------------------------------------------------------------
  std::string mlir = ReadFile(export_dir + "/exported_predict.stablehlo.mlir");
  std::string copts = ReadFile(export_dir + "/compile_options.pb");
  std::string meta = ReadFile(export_dir + "/meta.json", /*binary=*/false);
  auto shape = JsonIntArray(meta, "input_shape");
  if (shape.size() != 4) Die("input_shape must be rank 4");

  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = mlir.data();
  program.code_size = mlir.size();
  program.format = "mlir";
  program.format_size = 4;

  PJRT_Client_Compile_Args comp;
  std::memset(&comp, 0, sizeof(comp));
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = client;
  comp.program = &program;
  comp.compile_options = copts.data();
  comp.compile_options_size = copts.size();
  auto t0 = std::chrono::steady_clock::now();
  Check(g_api->PJRT_Client_Compile(&comp), "compile");
  PJRT_LoadedExecutable* exec = comp.executable;
  double compile_s = std::chrono::duration<double>(
      std::chrono::steady_clock::now() - t0).count();
  std::printf("compiled StableHLO (%.1f KB) in %.2fs\n", mlir.size() / 1024.0,
              compile_s);

  // --- input buffer ---------------------------------------------------------
  // raw-input exports (--export-raw-input) take uint8 [0, 255] pixels with
  // normalization baked into the program — 4x less wire traffic per frame
  const std::string in_dtype = JsonString(meta, "input_dtype", "float32");
  const bool u8 = in_dtype == "uint8";
  if (!u8 && in_dtype != "float32") Die("unsupported input_dtype " + in_dtype);
  const size_t esize = u8 ? 1 : sizeof(float);
  size_t elems = 1;
  std::vector<int64_t> dims;
  for (long d : shape) { dims.push_back(d); elems *= static_cast<size_t>(d); }
  std::vector<char> image(elems * esize, 0);
  if (!image_path.empty()) {
    std::string raw = ReadFile(image_path);
    if (raw.size() != elems * esize)
      Die("image file size mismatch: want " + std::to_string(elems * esize) +
          " bytes, got " + std::to_string(raw.size()));
    std::memcpy(image.data(), raw.data(), raw.size());
  }

  PJRT_Client_BufferFromHostBuffer_Args bargs;
  std::memset(&bargs, 0, sizeof(bargs));
  bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  bargs.client = client;
  bargs.data = image.data();
  bargs.type = u8 ? PJRT_Buffer_Type_U8 : PJRT_Buffer_Type_F32;
  bargs.dims = dims.data();
  bargs.num_dims = dims.size();
  bargs.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  bargs.device = device;
  Check(g_api->PJRT_Client_BufferFromHostBuffer(&bargs), "h2d");
  Await(bargs.done_with_host_buffer, "h2d event");
  PJRT_Buffer* input = bargs.buffer;

  // --- output arity ---------------------------------------------------------
  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = exec;
  Check(g_api->PJRT_LoadedExecutable_GetExecutable(&gargs), "get executable");
  PJRT_Executable_NumOutputs_Args nargs;
  std::memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  Check(g_api->PJRT_Executable_NumOutputs(&nargs), "num outputs");
  size_t num_outputs = nargs.num_outputs;
  std::printf("executable outputs: %zu\n", num_outputs);

  // --- execute (timed) ------------------------------------------------------
  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  // the input is reused every iteration; forbid donation
  int64_t non_donatable[] = {0};
  opts.non_donatable_input_indices = non_donatable;
  opts.num_non_donatable_input_indices = 1;

  PJRT_Buffer* const arg_list[] = {input};
  PJRT_Buffer* const* const argument_lists[] = {arg_list};

  // One in-flight frame: its (not yet fetched) output buffers + the device
  // completion event the fetch must wait behind.
  struct InFlight {
    std::vector<PJRT_Buffer*> outs;
    PJRT_Event* done = nullptr;
  };

  auto dispatch = [&]() {
    InFlight f;
    f.outs.assign(num_outputs, nullptr);
    PJRT_Buffer** output_list = f.outs.data();
    PJRT_LoadedExecutable_Execute_Args eargs;
    std::memset(&eargs, 0, sizeof(eargs));
    eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    eargs.executable = exec;
    eargs.options = &opts;
    eargs.argument_lists = argument_lists;
    eargs.num_devices = 1;
    eargs.num_args = 1;
    eargs.output_lists = &output_list;
    PJRT_Event** events = &f.done;
    eargs.device_complete_events = events;
    // output buffer pointers are written synchronously during Execute, so
    // moving f (vector data pointer is move-stable) afterwards is safe
    Check(g_api->PJRT_LoadedExecutable_Execute(&eargs), "execute");
    return f;
  };

  auto complete = [&](InFlight& f, bool keep_outputs) {
    Await(f.done, "execute event");
    // Deployment semantics: every frame's detections are consumed by the
    // host, so fetch one (tiny) output each iteration. This is also what
    // keeps the timing honest whatever the plugin's completion events
    // mean: a D2H cannot complete before the bytes exist.
    if (num_outputs == 0 || f.outs[num_outputs - 1] == nullptr)
      Die("executable produced no outputs to fetch; timing would be "
          "event-only and unreliable");
    (void)BufferToHost(f.outs[num_outputs - 1]);
    if (!keep_outputs) {
      for (auto*& b : f.outs) {
        if (!b) continue;
        PJRT_Buffer_Destroy_Args dargs;
        std::memset(&dargs, 0, sizeof(dargs));
        dargs.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        dargs.buffer = b;
        Check(g_api->PJRT_Buffer_Destroy(&dargs), "buffer destroy");
        b = nullptr;
      }
    }
  };

  {
    InFlight w = dispatch();  // warmup
    complete(w, false);
  }
  if (depth < 1) depth = 1;
  // Pipelined timed loop: up to `depth` frames in flight; frame i's fetch
  // overlaps frame i+1..i+depth-1's execution. depth=1 == sequential.
  std::vector<InFlight> queue;  // FIFO, small (<= depth)
  std::vector<PJRT_Buffer*> last_outs;  // kept for detection printing
  int completed = 0;
  auto complete_front = [&]() {
    bool last = completed == iters - 1;  // final frame: keep for printing
    complete(queue.front(), last);
    if (last) last_outs = std::move(queue.front().outs);
    queue.erase(queue.begin());
    ++completed;
  };
  t0 = std::chrono::steady_clock::now();
  for (int i = 0; i < iters; ++i) {
    queue.push_back(dispatch());
    if (static_cast<int>(queue.size()) >= depth) complete_front();
  }
  while (!queue.empty()) complete_front();
  double dt = std::chrono::duration<double>(
      std::chrono::steady_clock::now() - t0).count();
  double fps = shape[0] * iters / dt;
  std::printf("timing: %d iters, batch %ld, depth %d: %.2f img/s "
              "(%.2f ms/batch, incl. per-frame D2H)\n",
              iters, shape[0], depth, fps, 1000.0 * dt / iters);

  // --- print detections from the last run ----------------------------------
  if (num_outputs >= 4 && last_outs.size() >= 4) {
    HostOutput boxes = BufferToHost(last_outs[0]);
    HostOutput classes = BufferToHost(last_outs[1]);
    HostOutput scores = BufferToHost(last_outs[2]);
    HostOutput valid = BufferToHost(last_outs[3]);
    const float* bx = reinterpret_cast<const float*>(boxes.bytes.data());
    const int32_t* cl = reinterpret_cast<const int32_t*>(classes.bytes.data());
    const float* sc = reinterpret_cast<const float*>(scores.bytes.data());
    const char* va = valid.bytes.data();
    int64_t n = boxes.dims.size() >= 2 ? boxes.dims[1] : 0;
    int shown = 0;
    for (int64_t i = 0; i < n && shown < 10; ++i) {
      if (!va[i]) continue;
      std::printf("det[%lld] cls=%d score=%.3f box=(%.1f, %.1f, %.1f, %.1f)\n",
                  static_cast<long long>(i), cl[i], sc[i], bx[i * 4 + 0],
                  bx[i * 4 + 1], bx[i * 4 + 2], bx[i * 4 + 3]);
      ++shown;
    }
    if (shown == 0) std::printf("no detections above threshold\n");
  }

  std::printf("OK\n");
  return 0;
}
