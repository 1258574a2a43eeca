// Stub PJRT plugin — TEST FIXTURE for pjrt_runner (tests/test_pjrt_runner.py).
//
// No real CPU PJRT plugin .so ships in this image (jaxlib's CPU client is
// linked into the Python extension, not exported as a C-API plugin), so CI
// exercises the runner's full PJRT control flow — plugin load, client
// create, compile, H2D, execute, D2H, detection printing — against this
// in-memory implementation of exactly the C-API surface the runner uses.
// "Compile" accepts any program; "execute" returns canned detections the
// test asserts on. Real-hardware runs use the TPU plugin (see the
// TPU-gated test); this stub only validates the runner binary's ABI usage
// and control flow, the same role as a fake backend in the Python suite.

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

struct PJRT_Error {
  std::string message;
};
struct PJRT_Event {};
struct PJRT_Device {};
struct PJRT_Client {
  PJRT_Device device;
  PJRT_Device* devices[1];
};
struct PJRT_Executable {
  size_t num_outputs = 4;
};
struct PJRT_LoadedExecutable {
  PJRT_Executable executable;
  int64_t batch = 1;
};
struct PJRT_Buffer {
  std::vector<int64_t> dims;
  // Stored in a COLUMN-MAJOR device layout (dim 0 innermost) — real TPU
  // buffers live in a tiled device layout too, and the r2 hardware run
  // surfaced a runner bug CI could not catch while the stub served plain
  // row-major bytes: PJRT_Buffer_ToHostBuffer without an explicit
  // host_layout returns DEVICE-layout bytes (transposed boxes). The stub
  // now reproduces that contract: a dense row-major host_layout request
  // gets converted data; no/other layout gets the raw device bytes.
  std::vector<char> device_data;
  size_t esize = sizeof(float);
};

namespace {

constexpr int64_t kNumBoxes = 8;

// Convert between logical row-major bytes and the stub's column-major
// device layout (dim 0 innermost). to_device=true: src is row-major.
void ConvertLayout(const char* src, char* dst,
                   const std::vector<int64_t>& dims, size_t esize,
                   bool to_device) {
  const size_t rank = dims.size();
  size_t total = 1;
  for (int64_t d : dims) total *= static_cast<size_t>(d);
  if (rank <= 1) {
    std::memcpy(dst, src, total * esize);
    return;
  }
  std::vector<size_t> cstride(rank);
  cstride[0] = 1;
  for (size_t i = 1; i < rank; ++i)
    cstride[i] = cstride[i - 1] * static_cast<size_t>(dims[i - 1]);
  std::vector<int64_t> idx(rank, 0);
  for (size_t n = 0; n < total; ++n) {  // n = row-major linear index
    size_t col = 0;
    for (size_t i = 0; i < rank; ++i) col += idx[i] * cstride[i];
    const char* s = src + (to_device ? n : col) * esize;
    char* d = dst + (to_device ? col : n) * esize;
    std::memcpy(d, s, esize);
    for (size_t i = rank; i-- > 0;) {  // increment row-major multi-index
      if (++idx[i] < dims[i]) break;
      idx[i] = 0;
    }
  }
}

PJRT_Buffer* MakeDeviceBuffer(std::vector<int64_t> dims, const void* rowmajor,
                              size_t esize) {
  auto* buf = new PJRT_Buffer;
  buf->dims = std::move(dims);
  buf->esize = esize;
  size_t total = esize;
  for (int64_t d : buf->dims) total *= static_cast<size_t>(d);
  buf->device_data.resize(total);
  ConvertLayout(static_cast<const char*>(rowmajor), buf->device_data.data(),
                buf->dims, esize, /*to_device=*/true);
  return buf;
}

void ErrorMessage(PJRT_Error_Message_Args* args) {
  args->message = args->error->message.c_str();
  args->message_size = args->error->message.size();
}

void ErrorDestroy(PJRT_Error_Destroy_Args* args) { delete args->error; }

PJRT_Error* EventAwait(PJRT_Event_Await_Args* args) { return nullptr; }

PJRT_Error* EventDestroy(PJRT_Event_Destroy_Args* args) {
  delete args->event;
  return nullptr;
}

PJRT_Error* ClientCreate(PJRT_Client_Create_Args* args) {
  auto* client = new PJRT_Client;
  client->devices[0] = &client->device;
  args->client = client;
  return nullptr;
}

PJRT_Error* AddressableDevices(PJRT_Client_AddressableDevices_Args* args) {
  args->addressable_devices = args->client->devices;
  args->num_addressable_devices = 1;
  return nullptr;
}

PJRT_Error* Compile(PJRT_Client_Compile_Args* args) {
  if (args->program == nullptr || args->program->code_size == 0)
    return new PJRT_Error{"empty program"};
  args->executable = new PJRT_LoadedExecutable;
  return nullptr;
}

PJRT_Error* BufferFromHost(PJRT_Client_BufferFromHostBuffer_Args* args) {
  std::vector<int64_t> dims(args->dims, args->dims + args->num_dims);
  size_t esize = args->type == PJRT_Buffer_Type_U8 ? 1 : sizeof(float);
  size_t elems = 1;
  for (size_t i = 0; i < args->num_dims; ++i) elems *= args->dims[i];
  std::vector<char> zero;
  const void* src = args->data;
  if (src == nullptr) {
    zero.assign(elems * esize, 0);
    src = zero.data();
  }
  args->buffer = MakeDeviceBuffer(std::move(dims), src, esize);
  args->done_with_host_buffer = new PJRT_Event;
  return nullptr;
}

PJRT_Error* GetExecutable(PJRT_LoadedExecutable_GetExecutable_Args* args) {
  args->executable = &args->loaded_executable->executable;
  return nullptr;
}

PJRT_Error* NumOutputs(PJRT_Executable_NumOutputs_Args* args) {
  args->num_outputs = args->executable->num_outputs;
  return nullptr;
}

PJRT_Error* Execute(PJRT_LoadedExecutable_Execute_Args* args) {
  if (args->num_devices != 1 || args->num_args != 1)
    return new PJRT_Error{"stub expects 1 device, 1 arg"};
  const int64_t b = args->executable->batch;

  // canned detections authored ROW-major; MakeDeviceBuffer stores them in
  // the column-major device layout, so a runner that forgets to request a
  // row-major host_layout reads interleaved garbage (the r2 hardware bug)
  std::vector<float> bx(b * kNumBoxes * 4, 0.0f);
  float det0[4] = {10.0f, 20.0f, 30.0f, 40.0f};
  float det1[4] = {50.0f, 60.0f, 70.0f, 80.0f};
  std::memcpy(&bx[0], det0, sizeof(det0));
  std::memcpy(&bx[4], det1, sizeof(det1));
  auto* boxes = MakeDeviceBuffer({b, kNumBoxes, 4}, bx.data(), sizeof(float));

  std::vector<int32_t> cl(b * kNumBoxes, 0);
  cl[1] = 1;
  auto* classes = MakeDeviceBuffer({b, kNumBoxes}, cl.data(),
                                   sizeof(int32_t));

  std::vector<float> sc(b * kNumBoxes, 0.0f);
  sc[0] = 0.9f;
  sc[1] = 0.8f;
  auto* scores = MakeDeviceBuffer({b, kNumBoxes}, sc.data(), sizeof(float));

  std::vector<char> va(b * kNumBoxes, 0);
  va[0] = 1;
  va[1] = 1;
  auto* valid = MakeDeviceBuffer({b, kNumBoxes}, va.data(), 1);

  args->output_lists[0][0] = boxes;
  args->output_lists[0][1] = classes;
  args->output_lists[0][2] = scores;
  args->output_lists[0][3] = valid;
  if (args->device_complete_events)
    args->device_complete_events[0] = new PJRT_Event;
  return nullptr;
}

PJRT_Error* BufferDimensions(PJRT_Buffer_Dimensions_Args* args) {
  args->dims = args->buffer->dims.data();
  args->num_dims = args->buffer->dims.size();
  return nullptr;
}

bool IsRowMajorRequest(const PJRT_Buffer_MemoryLayout* layout, size_t rank) {
  if (layout == nullptr ||
      layout->type != PJRT_Buffer_MemoryLayout_Type_Tiled ||
      layout->tiled.minor_to_major_size != rank)
    return false;
  for (size_t i = 0; i < rank; ++i)
    if (layout->tiled.minor_to_major[i] !=
        static_cast<int64_t>(rank - 1 - i))
      return false;
  return true;
}

PJRT_Error* ToHostBuffer(PJRT_Buffer_ToHostBuffer_Args* args) {
  if (args->dst == nullptr) {
    args->dst_size = args->src->device_data.size();
    return nullptr;
  }
  if (IsRowMajorRequest(args->host_layout, args->src->dims.size())) {
    // explicit dense row-major request: convert from the device layout —
    // the contract the real TPU plugin honors
    ConvertLayout(args->src->device_data.data(),
                  static_cast<char*>(args->dst), args->src->dims,
                  args->src->esize, /*to_device=*/false);
  } else {
    // no (or non-row-major) host layout: serve raw DEVICE-layout bytes,
    // exactly what a real TPU plugin did when the r2 runner omitted
    // host_layout and read transposed boxes
    std::memcpy(args->dst, args->src->device_data.data(),
                args->src->device_data.size());
  }
  args->event = new PJRT_Event;
  return nullptr;
}

PJRT_Error* BufferDestroy(PJRT_Buffer_Destroy_Args* args) {
  delete args->buffer;
  return nullptr;
}

PJRT_Api MakeApi() {
  PJRT_Api api;
  std::memset(&api, 0, sizeof(api));
  api.struct_size = PJRT_Api_STRUCT_SIZE;
  api.pjrt_api_version.major_version = PJRT_API_MAJOR;
  api.pjrt_api_version.minor_version = PJRT_API_MINOR;
  api.PJRT_Error_Message = ErrorMessage;
  api.PJRT_Error_Destroy = ErrorDestroy;
  api.PJRT_Event_Await = EventAwait;
  api.PJRT_Event_Destroy = EventDestroy;
  api.PJRT_Client_Create = ClientCreate;
  api.PJRT_Client_AddressableDevices = AddressableDevices;
  api.PJRT_Client_Compile = Compile;
  api.PJRT_Client_BufferFromHostBuffer = BufferFromHost;
  api.PJRT_LoadedExecutable_GetExecutable = GetExecutable;
  api.PJRT_Executable_NumOutputs = NumOutputs;
  api.PJRT_LoadedExecutable_Execute = Execute;
  api.PJRT_Buffer_Dimensions = BufferDimensions;
  api.PJRT_Buffer_ToHostBuffer = ToHostBuffer;
  api.PJRT_Buffer_Destroy = BufferDestroy;
  return api;
}

PJRT_Api g_stub_api = MakeApi();

}  // namespace

extern "C" __attribute__((visibility("default"))) const PJRT_Api*
GetPjrtApi() { return &g_stub_api; }
