/* singa_core — native runtime for singa_tpu.
 *
 * Capability parity with the reference's native core (SURVEY.md §2.2
 * rows 1-5; language evidence /root/reference/.gitignore:1-28 — C++
 * shared-library build artifacts):
 *   - tensor_math_cpp : eager CPU kernels for the CppCPU debug device
 *   - scheduler       : graph topo-sort + liveness memory planning
 *   - dataloader      : threaded shuffle/batch/prefetch pipeline
 *
 * The TPU compute path is XLA (that is the idiomatic native path to the
 * MXU); this library is the host-side runtime around it.  Exposed as a
 * plain C API consumed via ctypes (no pybind11 in the image).
 */
#ifndef SINGA_CORE_H_
#define SINGA_CORE_H_

#include <cstdint>
#include <cstddef>

extern "C" {

/* ---------------- tensor_math_cpp ---------------- */
/* All kernels: float32, contiguous row-major. */
void sg_gemm(const float* a, const float* b, float* c,
             int64_t m, int64_t k, int64_t n,
             int transa, int transb, float alpha, float beta);
void sg_add(const float* a, const float* b, float* out, int64_t n);
void sg_sub(const float* a, const float* b, float* out, int64_t n);
void sg_mul(const float* a, const float* b, float* out, int64_t n);
void sg_div(const float* a, const float* b, float* out, int64_t n);
void sg_axpy(float alpha, const float* x, float* y, int64_t n); /* y += a*x */
void sg_scale(float alpha, float* x, int64_t n);
void sg_relu(const float* a, float* out, int64_t n);
void sg_relu_grad(const float* a, const float* dy, float* out, int64_t n);
void sg_sigmoid(const float* a, float* out, int64_t n);
void sg_tanh(const float* a, float* out, int64_t n);
void sg_exp(const float* a, float* out, int64_t n);
void sg_softmax(const float* a, float* out, int64_t rows, int64_t cols);
void sg_sum(const float* a, float* out, int64_t n); /* out[0] = sum */
void sg_conv2d_nhwc(const float* x, const float* w, float* y,
                    int64_t N, int64_t H, int64_t W, int64_t C,
                    int64_t KH, int64_t KW, int64_t OC,
                    int64_t sh, int64_t sw, int64_t ph, int64_t pw);
void sg_sgd_update(float* param, const float* grad, float* mom,
                   float lr, float momentum, float weight_decay, int64_t n);

/* ---------------- pjrt_device ---------------- */
/* Native TpuDevice touchpoint: load a PJRT plugin (libtpu.so), do the
 * C-API version handshake, read plugin attributes; client creation is
 * opt-in (blocks while another client holds the chip).  pjrt_device.cc. */
int64_t sg_pjrt_load(const char* so_path, int init, char* err,
                     int64_t errcap);
int64_t sg_pjrt_api_version(int64_t h, int32_t* major, int32_t* minor);
int     sg_pjrt_init_error(int64_t h, char* buf, int64_t cap);
int64_t sg_pjrt_attr_count(int64_t h);
int     sg_pjrt_attr_get(int64_t h, int64_t i, char* name, int64_t ncap,
                         char* val, int64_t vcap);
int64_t sg_pjrt_client_create(int64_t h, char* err, int64_t errcap);
int64_t sg_pjrt_client_device_count(int64_t c);
int     sg_pjrt_client_platform(int64_t c, char* buf, int64_t cap);
int     sg_pjrt_device_desc(int64_t c, int64_t i, char* buf, int64_t cap);
void    sg_pjrt_client_destroy(int64_t c);
void    sg_pjrt_unload(int64_t h);

/* ---------------- scheduler ---------------- */
/* Build a graph of ops; topo-sort; plan buffer reuse by liveness.
 * Handles are opaque int64 ids. */
int64_t sg_graph_new(void);
void    sg_graph_free(int64_t g);
/* add node: nin input buffer-ids, nout output buffer-ids (caller-chosen
 * dense ints), returns node id or -1 */
int64_t sg_graph_add_node(int64_t g, const char* name,
                          const int64_t* in_bufs, int64_t nin,
                          const int64_t* out_bufs, int64_t nout,
                          const int64_t* buf_sizes_out, int64_t flops);
/* topo order of node ids into out[n]; returns n or -1 on cycle */
int64_t sg_graph_toposort(int64_t g, int64_t* out, int64_t cap);
/* liveness-based memory plan: assigns each buffer an offset in a shared
 * arena (first-fit over free intervals). Returns arena bytes needed.
 * offsets[i] receives the offset of buffer id i (cap entries). */
int64_t sg_graph_plan_memory(int64_t g, int64_t* offsets, int64_t cap);
int64_t sg_graph_num_nodes(int64_t g);
int64_t sg_graph_total_flops(int64_t g);

/* ---------------- dataloader ---------------- */
/* In-memory dataset of (x, y) float32/int32 arrays; background threads
 * produce shuffled batches into a bounded ring buffer. */
int64_t sg_loader_new(const float* x, const int32_t* y,
                      int64_t n, int64_t x_stride /* floats per sample */,
                      int64_t batch, int shuffle, uint64_t seed,
                      int drop_last, int workers, int prefetch);
/* blocks until a batch is ready; writes batch data and returns the
 * actual batch size, 0 at epoch end (loader rewinds + reshuffles), or
 * -1 on error */
int64_t sg_loader_next(int64_t h, float* x_out, int32_t* y_out);
void    sg_loader_free(int64_t h);
int64_t sg_loader_batches_per_epoch(int64_t h);

/* ---------------- allocator (host staging pool) ---------------- */
void*  sg_pool_alloc(size_t bytes);
void   sg_pool_free(void* p);
size_t sg_pool_bytes_in_use(void);
size_t sg_pool_bytes_reserved(void);
void   sg_pool_trim(void);

const char* sg_version(void);

} /* extern "C" */

#endif /* SINGA_CORE_H_ */
