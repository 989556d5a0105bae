// Fluent C++ wrapper over the native PJRT dispatch core
// (libmxtpu_pjrt.so — include/mxtpu/pjrt_c_api.h): load a plugin,
// compile an mx.deploy StableHLO bundle, run inference with
// device-resident buffers.  Unlike mxnet-cpp's Predictor (which fronts
// the full framework through the embedded interpreter), this path has
// NO Python anywhere — it is the latency-critical deploy shape.
//
//   mxnet_cpp::PjrtPredictor pred("/path/to/libtpu.so",
//                                 "model.mxshlo");
//   auto out = pred.Forward({{data.data(), {2, 8}}});
#ifndef MXNET_CPP_PJRT_PREDICTOR_H_
#define MXNET_CPP_PJRT_PREDICTOR_H_

#include <cstdint>
#include <stdexcept>
#include <string>
#include <utility>
#include <vector>

#include "mxtpu/pjrt_c_api.h"

namespace mxnet_cpp {

class PjrtPredictor {
 public:
  struct Input {
    const float* data;
    std::vector<int64_t> dims;
  };

  PjrtPredictor(const std::string& plugin_path,
                const std::string& bundle_path) {
    client_ = MXTPUPjrtLoad(plugin_path.c_str());
    if (client_ == nullptr) Throw("MXTPUPjrtLoad");
    exec_ = MXTPUPjrtPredictCreate(client_, bundle_path.c_str());
    if (exec_ == nullptr) {
      MXTPUPjrtFree(client_);
      client_ = nullptr;
      Throw("MXTPUPjrtPredictCreate");
    }
  }

  ~PjrtPredictor() {
    // lifetime contract: executable before client
    if (exec_ != nullptr) MXTPUPjrtExecFree(exec_);
    if (client_ != nullptr) MXTPUPjrtFree(client_);
  }

  PjrtPredictor(const PjrtPredictor&) = delete;
  PjrtPredictor& operator=(const PjrtPredictor&) = delete;

  int NumOutputs() const { return MXTPUPjrtExecNumOutputs(exec_); }

  // One float32 forward: host inputs in, host outputs out (each output
  // as a flat vector + its dims).
  std::vector<std::pair<std::vector<float>, std::vector<int64_t>>>
  Forward(const std::vector<Input>& inputs) {
    std::vector<void*> bufs;
    auto cleanup = [&bufs]() {
      for (void* b : bufs) MXTPUPjrtBufferFree(b);
    };
    for (const auto& in : inputs) {
      void* b = MXTPUPjrtBufferFromHost(
          client_, in.data, /*F32*/ 11, in.dims.data(),
          (int)in.dims.size(), 0);
      if (b == nullptr) {
        cleanup();
        Throw("MXTPUPjrtBufferFromHost");
      }
      bufs.push_back(b);
    }
    int n_out = NumOutputs();
    std::vector<void*> outs((size_t)(n_out > 0 ? n_out : 1), nullptr);
    int got = MXTPUPjrtExecute(exec_, bufs.data(), (int)bufs.size(),
                               outs.data(), (int)outs.size());
    cleanup();
    bufs.clear();
    if (got < 0) Throw("MXTPUPjrtExecute");
    std::vector<std::pair<std::vector<float>, std::vector<int64_t>>>
        result;
    for (int i = 0; i < got; ++i) {
      int rank = MXTPUPjrtBufferDims(outs[i], nullptr, 0);
      std::vector<int64_t> dims((size_t)(rank > 0 ? rank : 0));
      int nd = rank <= 0 ? rank
                         : MXTPUPjrtBufferDims(outs[i], dims.data(),
                                               rank);
      int64_t nbytes = MXTPUPjrtBufferToHost(outs[i], nullptr, 0);
      std::vector<float> host;
      bool ok = rank >= 0 && nd >= 0 && nbytes >= 0 &&
                nbytes % (int64_t)sizeof(float) == 0;
      if (ok) {
        host.resize((size_t)nbytes / sizeof(float));
        ok = MXTPUPjrtBufferToHost(outs[i], host.data(), nbytes) ==
             nbytes;
      }
      if (!ok) {
        for (int j = i; j < got; ++j) MXTPUPjrtBufferFree(outs[j]);
        Throw("MXTPUPjrtBufferToHost");
      }
      result.emplace_back(std::move(host), std::move(dims));
      MXTPUPjrtBufferFree(outs[i]);
    }
    return result;
  }

 private:
  static void Throw(const char* where) {
    throw std::runtime_error(std::string(where) + ": " +
                             MXTPUPjrtLastError());
  }

  void* client_ = nullptr;
  void* exec_ = nullptr;
};

}  // namespace mxnet_cpp

#endif  // MXNET_CPP_PJRT_PREDICTOR_H_
