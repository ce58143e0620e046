/* pjrt_device — native TpuDevice touchpoint over the PJRT C API.
 *
 * SURVEY.md §7.1 stance: the TPU entry is PJRT.  The COMPUTE path
 * stays JAX/XLA in-process (a chip belongs to one client at a time, so
 * a second C++ client would contend with jax's own for it — see
 * docs/native_tpu_device.md), but
 * the device layer's native surface is real: this module dlopens a
 * PJRT plugin (libtpu.so or any other PJRT_Api provider), validates
 * the C-API version handshake, surfaces plugin attributes
 * (xla_version, stablehlo versions, ...), and — explicitly opt-in,
 * because client creation blocks while another client holds the chip —
 * creates a client to enumerate devices and their descriptions.
 *
 * Compiled against the official pjrt_c_api.h shipped in this image
 * (tensorflow/include/xla/pjrt/c/pjrt_c_api.h).  Exposed as a plain C
 * API consumed via ctypes (no pybind11 in the image).
 */

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct Plugin {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  std::string init_error;  // empty if PJRT_Plugin_Initialize succeeded
  bool alive = false;
};

struct ClientHandle {
  PJRT_Client* client = nullptr;
  int64_t plugin = -1;
  bool alive = false;
};

std::mutex g_mu;
std::vector<Plugin> g_plugins;
std::vector<ClientHandle> g_clients;

void copy_str(const char* src, size_t n, char* dst, int64_t cap) {
  if (!dst || cap <= 0) return;
  size_t m = (n < static_cast<size_t>(cap) - 1) ? n : static_cast<size_t>(cap) - 1;
  if (src && m) std::memcpy(dst, src, m);
  dst[m] = '\0';
}

/* Collect an error's message and destroy it.  Returns true if err was
 * non-null (i.e. the call failed). */
bool take_error(const PJRT_Api* api, PJRT_Error* err, std::string* out) {
  if (!err) return false;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  if (out) out->assign(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return true;
}

Plugin* get_plugin(int64_t h) {
  if (h < 0 || h >= static_cast<int64_t>(g_plugins.size())) return nullptr;
  Plugin* p = &g_plugins[h];
  return p->alive ? p : nullptr;
}

ClientHandle* get_client(int64_t c) {
  if (c < 0 || c >= static_cast<int64_t>(g_clients.size())) return nullptr;
  ClientHandle* ch = &g_clients[c];
  return ch->alive ? ch : nullptr;
}

}  // namespace

extern "C" {

/* Load a PJRT plugin shared object; resolve GetPjrtApi; optionally run
 * PJRT_Plugin_Initialize (init!=0).  Returns a handle >= 0, or -1 with
 * a message in err. */
int64_t sg_pjrt_load(const char* so_path, int init, char* err,
                     int64_t errcap) {
  std::lock_guard<std::mutex> lock(g_mu);
  Plugin p;
  p.dl = dlopen(so_path, RTLD_NOW | RTLD_LOCAL);
  if (!p.dl) {
    const char* m = dlerror();
    if (!m) m = "dlopen failed";
    copy_str(m, std::strlen(m), err, errcap);
    return -1;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(p.dl, "GetPjrtApi"));
  if (!get_api) {
    copy_str("no GetPjrtApi symbol", 20, err, errcap);
    dlclose(p.dl);
    return -1;
  }
  p.api = get_api();
  if (!p.api || p.api->struct_size == 0) {
    copy_str("GetPjrtApi returned null/empty", 30, err, errcap);
    dlclose(p.dl);
    return -1;
  }
  if (init && p.api->PJRT_Plugin_Initialize) {
    PJRT_Plugin_Initialize_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    take_error(p.api, p.api->PJRT_Plugin_Initialize(&args), &p.init_error);
  }
  p.alive = true;
  g_plugins.push_back(p);
  return static_cast<int64_t>(g_plugins.size()) - 1;
}

/* C-API version handshake: fills major/minor; returns the PJRT_Api
 * struct_size (>0), or -1 on a bad handle. */
int64_t sg_pjrt_api_version(int64_t h, int32_t* major, int32_t* minor) {
  std::lock_guard<std::mutex> lock(g_mu);
  Plugin* p = get_plugin(h);
  if (!p) return -1;
  if (major) *major = p->api->pjrt_api_version.major_version;
  if (minor) *minor = p->api->pjrt_api_version.minor_version;
  return static_cast<int64_t>(p->api->struct_size);
}

/* Message from PJRT_Plugin_Initialize, or "" if it succeeded. */
int sg_pjrt_init_error(int64_t h, char* buf, int64_t cap) {
  std::lock_guard<std::mutex> lock(g_mu);
  Plugin* p = get_plugin(h);
  if (!p) return -1;
  copy_str(p->init_error.c_str(), p->init_error.size(), buf, cap);
  return 0;
}

int64_t sg_pjrt_attr_count(int64_t h) {
  std::lock_guard<std::mutex> lock(g_mu);
  Plugin* p = get_plugin(h);
  if (!p || !p->api->PJRT_Plugin_Attributes) return -1;
  PJRT_Plugin_Attributes_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Plugin_Attributes_Args_STRUCT_SIZE;
  if (take_error(p->api, p->api->PJRT_Plugin_Attributes(&args), nullptr))
    return -1;
  return static_cast<int64_t>(args.num_attributes);
}

/* Attribute i: name into `name`, value formatted as text into `val`.
 * Returns the PJRT_NamedValue_Type, or -1. */
int sg_pjrt_attr_get(int64_t h, int64_t i, char* name, int64_t ncap,
                     char* val, int64_t vcap) {
  std::lock_guard<std::mutex> lock(g_mu);
  Plugin* p = get_plugin(h);
  if (!p || !p->api->PJRT_Plugin_Attributes) return -1;
  PJRT_Plugin_Attributes_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Plugin_Attributes_Args_STRUCT_SIZE;
  if (take_error(p->api, p->api->PJRT_Plugin_Attributes(&args), nullptr))
    return -1;
  if (i < 0 || i >= static_cast<int64_t>(args.num_attributes)) return -1;
  const PJRT_NamedValue& nv = args.attributes[i];
  copy_str(nv.name, nv.name_size, name, ncap);
  char tmp[256];
  switch (nv.type) {
    case PJRT_NamedValue_kString:
      copy_str(nv.string_value, nv.value_size, val, vcap);
      break;
    case PJRT_NamedValue_kInt64:
      std::snprintf(tmp, sizeof(tmp), "%lld",
                    static_cast<long long>(nv.int64_value));
      copy_str(tmp, std::strlen(tmp), val, vcap);
      break;
    case PJRT_NamedValue_kInt64List: {
      std::string s;
      for (size_t j = 0; j < nv.value_size; ++j) {
        std::snprintf(tmp, sizeof(tmp), "%s%lld", j ? "," : "",
                      static_cast<long long>(nv.int64_array_value[j]));
        s += tmp;
      }
      copy_str(s.c_str(), s.size(), val, vcap);
      break;
    }
    case PJRT_NamedValue_kFloat:
      std::snprintf(tmp, sizeof(tmp), "%g",
                    static_cast<double>(nv.float_value));
      copy_str(tmp, std::strlen(tmp), val, vcap);
      break;
    case PJRT_NamedValue_kBool:
      copy_str(nv.bool_value ? "true" : "false", nv.bool_value ? 4 : 5,
               val, vcap);
      break;
    default:
      copy_str("?", 1, val, vcap);
  }
  return static_cast<int>(nv.type);
}

/* ---- client surface: OPT-IN ONLY (can block indefinitely while
 * another client holds the chip; callers must gate/timeout). ---- */

int64_t sg_pjrt_client_create(int64_t h, char* err, int64_t errcap) {
  const PJRT_Api* api = nullptr;
  {
    std::lock_guard<std::mutex> lock(g_mu);
    Plugin* p = get_plugin(h);
    if (!p || !p->api->PJRT_Client_Create) {
      copy_str("bad plugin handle", 17, err, errcap);
      return -1;
    }
    api = p->api;
  }
  // PJRT_Client_Create can block indefinitely (chip held elsewhere):
  // it must run OUTSIDE g_mu so the handshake-only calls
  // (api_version/attributes) stay responsive from other threads.
  PJRT_Client_Create_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  std::string msg;
  if (take_error(api, api->PJRT_Client_Create(&args), &msg)) {
    copy_str(msg.c_str(), msg.size(), err, errcap);
    return -1;
  }
  std::lock_guard<std::mutex> lock(g_mu);
  ClientHandle ch;
  ch.client = args.client;
  ch.plugin = h;
  ch.alive = true;
  g_clients.push_back(ch);
  return static_cast<int64_t>(g_clients.size()) - 1;
}

int64_t sg_pjrt_client_device_count(int64_t c) {
  std::lock_guard<std::mutex> lock(g_mu);
  ClientHandle* ch = get_client(c);
  if (!ch) return -1;
  Plugin* p = get_plugin(ch->plugin);
  if (!p) return -1;
  PJRT_Client_Devices_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Devices_Args_STRUCT_SIZE;
  args.client = ch->client;
  if (take_error(p->api, p->api->PJRT_Client_Devices(&args), nullptr))
    return -1;
  return static_cast<int64_t>(args.num_devices);
}

int sg_pjrt_client_platform(int64_t c, char* buf, int64_t cap) {
  std::lock_guard<std::mutex> lock(g_mu);
  ClientHandle* ch = get_client(c);
  if (!ch) return -1;
  Plugin* p = get_plugin(ch->plugin);
  if (!p) return -1;
  PJRT_Client_PlatformName_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = ch->client;
  if (take_error(p->api, p->api->PJRT_Client_PlatformName(&args), nullptr))
    return -1;
  copy_str(args.platform_name, args.platform_name_size, buf, cap);
  return 0;
}

/* Debug description of device i (kind, coords, ...). */
int sg_pjrt_device_desc(int64_t c, int64_t i, char* buf, int64_t cap) {
  std::lock_guard<std::mutex> lock(g_mu);
  ClientHandle* ch = get_client(c);
  if (!ch) return -1;
  Plugin* p = get_plugin(ch->plugin);
  if (!p) return -1;
  PJRT_Client_Devices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_Devices_Args_STRUCT_SIZE;
  dargs.client = ch->client;
  if (take_error(p->api, p->api->PJRT_Client_Devices(&dargs), nullptr))
    return -1;
  if (i < 0 || i >= static_cast<int64_t>(dargs.num_devices)) return -1;
  PJRT_Device_GetDescription_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_Device_GetDescription_Args_STRUCT_SIZE;
  gargs.device = dargs.devices[i];
  if (take_error(p->api, p->api->PJRT_Device_GetDescription(&gargs), nullptr))
    return -1;
  PJRT_DeviceDescription_DebugString_Args sargs;
  std::memset(&sargs, 0, sizeof(sargs));
  sargs.struct_size = PJRT_DeviceDescription_DebugString_Args_STRUCT_SIZE;
  sargs.device_description = gargs.device_description;
  if (take_error(p->api,
                 p->api->PJRT_DeviceDescription_DebugString(&sargs), nullptr))
    return -1;
  copy_str(sargs.debug_string, sargs.debug_string_size, buf, cap);
  return 0;
}

void sg_pjrt_client_destroy(int64_t c) {
  std::lock_guard<std::mutex> lock(g_mu);
  ClientHandle* ch = get_client(c);
  if (!ch) return;
  Plugin* p = get_plugin(ch->plugin);
  if (p && p->api->PJRT_Client_Destroy) {
    PJRT_Client_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = ch->client;
    take_error(p->api, p->api->PJRT_Client_Destroy(&args), nullptr);
  }
  ch->alive = false;
}

/* Note: the PJRT_Api and its attribute storage have process lifetime;
 * we keep the dl handle open (dlclose of a live PJRT plugin is unsafe)
 * and only mark the slot dead. */
void sg_pjrt_unload(int64_t h) {
  std::lock_guard<std::mutex> lock(g_mu);
  Plugin* p = get_plugin(h);
  if (p) p->alive = false;
}

}  // extern "C"
