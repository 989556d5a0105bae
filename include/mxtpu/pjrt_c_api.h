/*
 * Native PJRT dispatch core — public C surface (libmxtpu_pjrt.so).
 *
 * Load a PJRT plugin (libtpu.so), compile serialized
 * StableHLO, move buffers, execute — no Python anywhere.  Bundles come
 * from mxnet_tpu.deploy.export_stablehlo (see MXTPUPjrtPredictCreate).
 *
 * Lifetime contract (standard PJRT): free every buffer and executable
 * BEFORE freeing the client that produced them.
 *
 * All functions returning a pointer yield NULL on failure and set a
 * thread-local message readable via MXTPUPjrtLastError(); integer
 * returns use negative values for failure.
 */
#ifndef MXTPU_PJRT_C_API_H_
#define MXTPU_PJRT_C_API_H_

#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

/* thread-local message for the most recent failure */
const char* MXTPUPjrtLastError(void);

/* plugin load + client create; handle frees with MXTPUPjrtFree */
void* MXTPUPjrtLoad(const char* plugin_path);
void MXTPUPjrtFree(void* client);
int MXTPUPjrtDeviceCount(void* client);
/* writes a NUL-terminated (possibly truncated) name into out
 * (cap >= 1); returns the FULL name length (snprintf-style, so
 * truncation is detectable) or -1 */
int MXTPUPjrtPlatformName(void* client, char* out, int cap);

/* compile serialized code; format is "mlir" (StableHLO bytecode or
 * text) or "hlo" (HloModuleProto); options is a serialized
 * CompileOptionsProto (may be empty for defaults) */
void* MXTPUPjrtCompile(void* client, const char* code,
                       int64_t code_size, const char* format,
                       const char* options, int64_t options_size);
int MXTPUPjrtExecNumOutputs(void* exec);
void MXTPUPjrtExecFree(void* exec);

/* read an MXTPUSHLO2 bundle (mx.deploy.export_stablehlo) and compile
 * its raw StableHLO section with default options */
void* MXTPUPjrtPredictCreate(void* client, const char* bundle_path);

/* dtype codes = PJRT_Buffer_Type enum: 1 PRED, 2 S8, 3 S16, 4 S32,
 * 5 S64, 6 U8, 7 U16, 8 U32, 9 U64, 10 F16, 11 F32, 12 F64, 13 BF16 */
void* MXTPUPjrtBufferFromHost(void* client, const void* data,
                              int dtype, const int64_t* dims,
                              int ndims, int device_index);
void MXTPUPjrtBufferFree(void* buf);
int MXTPUPjrtBufferType(void* buf);
/* out == NULL: returns the rank; else fills out[0..ndim) (cap must
 * be >= rank) and returns ndim, or -1 */
int MXTPUPjrtBufferDims(void* buf, int64_t* out, int cap);
/* dst == NULL: returns required byte size; else copies and returns
 * the byte count, or -1 */
int64_t MXTPUPjrtBufferToHost(void* buf, void* dst, int64_t dst_size);

/* run on ONE device: n_args buffer handles in, output handles written
 * to out_bufs (capacity >= MXTPUPjrtExecNumOutputs); returns the
 * output count or -1.  Blocks until device completion. */
int MXTPUPjrtExecute(void* exec, void** arg_bufs, int n_args,
                     void** out_bufs, int out_cap);

#ifdef __cplusplus
}
#endif

#endif  /* MXTPU_PJRT_C_API_H_ */
