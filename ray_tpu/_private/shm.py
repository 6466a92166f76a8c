"""Python client for the C++ shared-memory object store (cpp/shm_store.cc).

Builds the .so on first use (g++ is a baked dependency), loads it via
ctypes, and exposes zero-copy create/get as memoryviews that numpy/jax wrap
without copies. Reference parity: CoreWorkerPlasmaStoreProvider
(plasma_store_provider.h:88) on the client side.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from dataclasses import dataclass
from typing import Optional

_CPP_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "cpp")
_LIB_PATH = os.path.abspath(os.path.join(_CPP_DIR, "libshm_store.so"))
_build_lock = threading.Lock()
_lib = None


def _load_lib():
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        # the binary is not in git: a fresh checkout builds it here. Present
        # means trusted — a copied tree's mtimes say nothing; after editing
        # shm_store.cc run `make -C cpp` (the Makefile rule renames the
        # finished library into place, so concurrent builders are safe)
        if not os.path.exists(_LIB_PATH):
            subprocess.run(
                ["make", "-s", "-C", os.path.abspath(_CPP_DIR)],
                check=True,
                capture_output=True,
            )
        lib = ctypes.CDLL(_LIB_PATH)
        lib.shm_store_connect.restype = ctypes.c_void_p
        lib.shm_store_connect.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.shm_store_create.restype = ctypes.c_void_p
        lib.shm_store_create.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
        ]
        lib.shm_store_get.restype = ctypes.c_void_p
        lib.shm_store_get.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.shm_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shm_store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p]
        lib.shm_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shm_store_evict.restype = ctypes.c_int64
        lib.shm_store_evict.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.shm_store_used.restype = ctypes.c_int64
        lib.shm_store_used.argtypes = [ctypes.c_void_p]
        lib.shm_store_capacity.restype = ctypes.c_int64
        lib.shm_store_capacity.argtypes = [ctypes.c_void_p]
        lib.shm_store_disconnect.argtypes = [ctypes.c_void_p]
        lib.shm_store_destroy.argtypes = [ctypes.c_char_p]
        lib.shm_store_pretouch.restype = ctypes.c_int64
        lib.shm_store_pretouch.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.shm_store_spill_pinned.restype = ctypes.c_int64
        lib.shm_store_spill_pinned.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p,
        ]
        _lib = lib
    return _lib


@dataclass
class ShmBufferRef:
    """Picklable handle to a shared-memory buffer (travels in envelopes).

    `node` is the cluster node whose local shm plane holds the primary copy
    ("" = head node); consumers on other nodes pull through the head
    (serialization.materialize)."""

    name: str
    size: int
    node: str = ""


_COPY_POOL = None
_COPY_POOL_LOCK = threading.Lock()
_PARALLEL_COPY_MIN = 32 << 20  # below this, thread fan-out costs more than it saves


def _reset_copy_pool_after_fork():
    """A forked child inherits the pool object but NOT its threads;
    submitting to it would queue work nobody drains (silent hang). The
    lock is replaced too — a fork while another thread held it would
    leave the child's copy permanently locked."""
    global _COPY_POOL, _COPY_POOL_LOCK
    _COPY_POOL = None
    _COPY_POOL_LOCK = threading.Lock()


os.register_at_fork(after_in_child=_reset_copy_pool_after_fork)


def _copy_chunk(ptr: int, data: memoryview, off: int, n: int) -> None:
    chunk = data[off : off + n]
    try:
        # zero-copy source view when the buffer is writable & contiguous
        src: object = (ctypes.c_char * n).from_buffer(chunk)
        ctypes.memmove(ptr + off, src, n)
        del src
    except (TypeError, BufferError):
        # read-only source (e.g. np.frombuffer views): numpy copies
        # straight into the mapping — no intermediate bytes object
        import numpy as np

        dst = np.ctypeslib.as_array((ctypes.c_ubyte * n).from_address(ptr + off))
        np.copyto(dst, np.frombuffer(chunk, dtype=np.uint8))


def _copy_into(ptr: int, data: memoryview, size: int) -> None:
    """Copy into the shm mapping, fanning large copies across threads —
    memmove/numpy copies release the GIL, so on multicore hosts the put
    path runs at aggregate memory bandwidth instead of one core's
    (reference: plasma clients get the same effect from parallel client
    processes writing disjoint objects)."""
    if data.itemsize != 1 or data.ndim != 1:
        # chunk offsets are BYTE offsets: flatten to a byte view first or
        # element-indexed slicing would copy the wrong regions
        data = data.cast("B")
    workers = min(8, os.cpu_count() or 1)
    if size < _PARALLEL_COPY_MIN or workers < 2:
        _copy_chunk(ptr, data, 0, size)
        return
    global _COPY_POOL
    with _COPY_POOL_LOCK:
        if _COPY_POOL is None:
            from concurrent.futures import ThreadPoolExecutor

            _COPY_POOL = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="shm-copy"
            )
    per = -(-size // workers)
    per += (-per) % (1 << 20)  # 1MB-align chunk boundaries
    futures = [
        _COPY_POOL.submit(_copy_chunk, ptr, data, off, min(per, size - off))
        for off in range(0, size, per)
    ]
    try:
        for f in futures:
            f.result()
    except BaseException:
        # one chunk failed: the caller will abandon the mapping, so NO
        # thread may still be writing into it (use-after-free) — cancel
        # what hasn't started and wait out what has
        from concurrent.futures import wait as _fwait

        for f in futures:
            f.cancel()
        _fwait(futures)
        raise


def _release_mapping(lib, handle, name_bytes, ptr):
    try:
        lib.shm_store_release(handle, name_bytes, ptr)
    except Exception:
        pass


def connect_for_session(session_dir: str):
    """Shared lazy-connect helper (head + workers): returns a ShmClient for
    the session, or None if disabled/unavailable. RAY_TPU_SHM_SESSION
    overrides the session name — agents give each node its own namespace so
    the per-node planes stay distinct even when tests colocate nodes on one
    machine."""
    from .config import GLOBAL_CONFIG as cfg

    session = os.environ.get("RAY_TPU_SHM_SESSION") or (
        os.path.basename(session_dir) if session_dir else ""
    )
    if not cfg.shm_store_enabled or not session:
        return None
    try:
        return ShmClient(session, cfg.shm_store_bytes)
    except Exception:
        return None


def attach_peer_plane(session: str) -> Optional["ShmClient"]:
    """Attach to ANOTHER node's shm plane when it lives on this machine
    (colocated test clusters, multi-agent hosts). shm_store_connect creates
    the store if missing, so probe the control segment first — blindly
    attaching to a dead peer would materialize a fresh empty store and mask
    the miss. Returns None when the peer plane is not on this host."""
    from .config import GLOBAL_CONFIG as cfg

    if not cfg.shm_store_enabled or not session:
        return None
    if not os.path.exists(f"/dev/shm/rtpu_{session}_ctrl"):
        return None
    try:
        return ShmClient(session, cfg.shm_store_bytes)
    except Exception:
        return None


class PendingBuffer:
    """An unsealed shm allocation exposing a writable view, so consumers can
    recv_into the destination slab directly (zero intermediate copy). Must
    end in commit() or abort(): unsealed objects are never LRU-evictable, so
    an abandoned mapping would leak capacity forever — a weakref finalizer
    aborts as a safety net if the owner drops the object without deciding."""

    __slots__ = (
        "_client", "name", "size", "_ptr", "view", "_done", "_finalizer",
        "__weakref__",
    )

    def __init__(self, client: "ShmClient", name: str, size: int, ptr: int):
        import weakref

        self._client = client
        self.name = name
        self.size = size
        self._ptr = ptr
        self.view = (
            memoryview((ctypes.c_char * size).from_address(ptr)).cast("B")
            if size
            else memoryview(bytearray(0))
        )
        self._done = False
        self._finalizer = weakref.finalize(
            self, _abort_pending, client.lib, client.handle, name.encode(), ptr
        )

    def commit(self) -> ShmBufferRef:
        if self._done:
            raise RuntimeError(f"pending buffer {self.name} already finished")
        self._done = True
        self._finalizer.detach()  # the sealed object must survive our GC
        self.view = memoryview(b"")  # drop the writable alias before sealing
        self._client.lib.shm_store_seal(self._client.handle, self.name.encode())
        self._client.lib.shm_store_release(
            self._client.handle, self.name.encode(), self._ptr
        )
        return ShmBufferRef(name=self.name, size=self.size)

    def abort(self) -> None:
        if self._done:
            return
        self._done = True
        self._finalizer.detach()
        self.view = memoryview(b"")
        _abort_pending(
            self._client.lib, self._client.handle, self.name.encode(), self._ptr
        )


def _abort_pending(lib, handle, name_bytes, ptr):
    """Release + delete an unsealed allocation (idempotent: delete of a
    missing/other-generation name is a no-op in the store)."""
    try:
        lib.shm_store_release(handle, name_bytes, ptr)
        lib.shm_store_delete(handle, name_bytes)
    except Exception:
        pass


class ShmClient:
    def __init__(self, session: str, capacity_bytes: int):
        self.session = session
        self.lib = _load_lib()
        self.handle = self.lib.shm_store_connect(session.encode(), capacity_bytes)
        if not self.handle:
            raise OSError("failed to connect to shm store")
        # node-local spill directory for pinned (lineage-free) objects under
        # memory pressure (reference: local_object_manager.h:110 spilling)
        from .config import GLOBAL_CONFIG as cfg

        self.spill_dir = os.path.join(cfg.session_dir_root, "spill", session)

    def _spill_file(self, name: str) -> str:
        return os.path.join(self.spill_dir, f"{name}.bin")

    def get_or_spilled(self, name: str) -> Optional[memoryview]:
        """Resolve a buffer from shm, falling back to its spill file — THE
        read path for every consumer (materialize, head fetch, agent fetch)
        so spill semantics can't diverge between them."""
        mv = self.get(ShmBufferRef(name=name, size=0))
        return mv if mv is not None else self.read_spilled(name)

    def read_spilled(self, name: str) -> Optional[memoryview]:
        """Zero-copy mmap of a spilled object's file (None if not spilled)."""
        import mmap as _mmap

        try:
            with open(self._spill_file(name), "rb") as f:
                size = os.fstat(f.fileno()).st_size
                if size == 0:
                    return memoryview(b"")
                mapped = _mmap.mmap(f.fileno(), size, access=_mmap.ACCESS_READ)
                return memoryview(mapped)
        except OSError:
            return None

    def create(
        self, name: str, data: memoryview | bytes, pin: bool = False
    ) -> Optional[ShmBufferRef]:
        """Copy `data` into a new sealed shm object. Returns None when the
        store is full even after LRU eviction of unpinned sealed objects —
        evicted ids are reconstructible from lineage (head.py), which is
        what makes producer-side eviction safe; `pin=True` marks data with
        NO lineage (ray.put) as never-evictable."""
        if self.handle is None:
            return None  # disconnected (shutdown): treat as store-full
        data = memoryview(data)
        size = data.nbytes
        ptr = self._alloc(name, size, pin)
        if not ptr:
            return None
        try:
            _copy_into(ptr, data, size)
        except BaseException:
            # an unsealed object is never LRU-evictable: without cleanup a
            # failed copy would leak its capacity forever
            self.lib.shm_store_release(self.handle, name.encode(), ptr)
            self.delete(name)
            raise
        self.lib.shm_store_seal(self.handle, name.encode())
        self.lib.shm_store_release(self.handle, name.encode(), ptr)
        return ShmBufferRef(name=name, size=size)

    def _alloc(self, name: str, size: int, pin: bool) -> Optional[int]:
        """Allocate an unsealed mapping, retrying through the LRU-evict /
        spill-pinned chain (plasma eviction contract: the head reconstructs
        evicted ids on demand; pinned lineage-free data spills to disk)."""
        ptr = self.lib.shm_store_create(self.handle, name.encode(), size, int(pin))
        if not ptr:
            want = max(size * 2, 1 << 20)
            if self.lib.shm_store_evict(self.handle, want) > 0:
                ptr = self.lib.shm_store_create(
                    self.handle, name.encode(), size, int(pin)
                )
            if not ptr:
                os.makedirs(self.spill_dir, exist_ok=True)
                if self.lib.shm_store_spill_pinned(
                    self.handle, want, self.spill_dir.encode()
                ) > 0:
                    ptr = self.lib.shm_store_create(
                        self.handle, name.encode(), size, int(pin)
                    )
        return ptr or None

    def create_uninitialized(
        self, name: str, size: int, pin: bool = False
    ) -> Optional[PendingBuffer]:
        """Allocate an UNSEALED buffer and hand back a writable view, so the
        bulk plane can recv_into the destination slab directly (the ≤1-copy
        pull path). The caller must commit() (seal, making it readable) or
        abort() (free the capacity). Returns None when the store is full
        even after eviction/spill, like create()."""
        if self.handle is None:
            return None
        ptr = self._alloc(name, size, pin)
        if not ptr:
            return None
        return PendingBuffer(self, name, size, ptr)

    def get(self, ref: ShmBufferRef) -> Optional[memoryview]:
        """Map a sealed object read-only, zero-copy. The mapping is unmapped
        and its pin dropped automatically when the last view dies (weakref
        finalizer on the backing ctypes buffer)."""
        if self.handle is None:
            return None  # disconnected (shutdown)
        import weakref

        size_out = ctypes.c_int64(0)
        ptr = self.lib.shm_store_get(self.handle, ref.name.encode(), ctypes.byref(size_out))
        if not ptr:
            return None
        buf = (ctypes.c_char * size_out.value).from_address(ptr)
        weakref.finalize(
            buf, _release_mapping, self.lib, self.handle, ref.name.encode(), ptr
        )
        # read-only: the page is PROT_READ; a writable view would SIGSEGV on
        # write instead of raising (numpy arrays unpickled from this buffer
        # correctly come out non-writeable, like the reference's plasma gets)
        return memoryview(buf).toreadonly()

    def delete(self, name: str):
        if self.handle is None:
            return  # disconnected (shutdown): late frees are no-ops
        self.lib.shm_store_delete(self.handle, name.encode())
        try:
            os.unlink(self._spill_file(name))
        except OSError:
            pass

    def used(self) -> int:
        if self.handle is None:
            return 0
        return self.lib.shm_store_used(self.handle)

    def capacity(self) -> int:
        if self.handle is None:
            return 0
        return self.lib.shm_store_capacity(self.handle)

    def evict(self, nbytes: int) -> int:
        if self.handle is None:
            return 0
        return self.lib.shm_store_evict(self.handle, nbytes)

    def pretouch_async(self):
        """Fault in the whole slab from a daemon thread (one caller per
        machine — the head does this at startup) so producers never pay
        first-touch zero-fill during puts. Skipped on single/dual-core
        hosts where the background faulting would contend with foreground
        work; there the allocator's warm-page reuse carries the load."""
        if (os.cpu_count() or 1) < 4:
            return
        handle = self.handle

        def _touch():
            try:
                if self.handle is not None:
                    # commit at most a 256MB prefix: enough for steady-state
                    # puts to stay warm without eagerly pinning the whole
                    # capacity in RAM on every node
                    self.lib.shm_store_pretouch(handle, 256 * 1024 * 1024)
            except Exception:
                pass

        threading.Thread(target=_touch, name="shm-pretouch", daemon=True).start()

    def disconnect(self):
        # The C handle is intentionally NOT freed: outstanding mapping
        # finalizers (weakref on ctypes buffers) may still call
        # shm_store_release with it after disconnect. One control-block mmap
        # per process leaks until exit — bounded and harmless.
        self.handle = None

    @staticmethod
    def destroy(session: str):
        """Remove the control segment AND sweep any leftover data segments
        (objects still referenced by crashed/leaked handles) + spill files."""
        _load_lib().shm_store_destroy(session.encode())
        import glob
        import shutil

        for path in glob.glob(f"/dev/shm/rtpu_{session}_*"):
            try:
                os.unlink(path)
            except OSError:
                pass
        from .config import GLOBAL_CONFIG as cfg

        shutil.rmtree(
            os.path.join(cfg.session_dir_root, "spill", session), ignore_errors=True
        )
