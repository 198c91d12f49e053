"""Native (C++) runtime components, with pure-Python fallbacks.

The reference delegates its host-side input machinery to torch DataLoader
worker processes and torch-xla's MpDeviceLoader threads (ref
data_loader.py:518-559); this package owns that machinery natively:
`token_loader.cpp` memory-maps tokenized corpora and assembles shuffled,
host-sharded batches on producer threads behind a C ABI.

The shared library builds on demand with g++ from the TRACKED source,
into the git-ignored `_native/_build/`, under a name that carries the
source's hash: an artifact that some other tree or toolchain left on disk
is never picked up for being newer. `TokenCorpusLoader` transparently falls
back to a NumPy implementation with IDENTICAL semantics (same permutation,
sharding, wraparound) when no toolchain is available, so behavior never
depends on the build; `loader.implementation` says which one runs.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "_native")
_SRC = os.path.join(_SRC_DIR, "token_loader.cpp")

_DTYPES = {np.dtype(np.uint16): 0, np.dtype(np.int32): 1, np.dtype(np.uint32): 2}

_lib = None
_lib_lock = threading.Lock()
_build_error: str | None = None


def _build_dir() -> str:
    # inside the checkout only (a read-only tree uses the NumPy loader)
    d = os.environ.get("ACCELERATE_TPU_NATIVE_CACHE") or os.path.join(
        _SRC_DIR, "_build")
    os.makedirs(d, mode=0o700, exist_ok=True)
    st = os.stat(d)
    # refuse a dir we don't own or that others can write: a planted .so in
    # a predictable shared path would be dlopened into the training process
    if (st.st_uid != os.getuid() or (st.st_mode & 0o022)
            or not os.access(d, os.W_OK)):
        raise OSError(f"no safe writable native build dir at {d}")
    return d


def _load_library():
    """Compile (once) and dlopen the native library; None if unavailable."""
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            with open(_SRC, "rb") as f:
                src_hash = hashlib.sha256(f.read()).hexdigest()[:16]
            so_path = os.path.join(_build_dir(), f"libatl-{src_hash}.so")
            if not os.path.exists(so_path):
                # unique temp output + atomic rename: N launcher workers can
                # race this build without anyone dlopening a half-written .so
                tmp_out = f"{so_path}.{os.getpid()}.tmp"
                cmd = [
                    "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                    "-pthread", _SRC, "-o", tmp_out,
                ]
                subprocess.run(cmd, check=True, capture_output=True, text=True)
                os.replace(tmp_out, so_path)
            lib = ctypes.CDLL(so_path)
        except (OSError, subprocess.CalledProcessError, FileNotFoundError) as e:
            _build_error = getattr(e, "stderr", None) or str(e)
            return None
        lib.atl_open.restype = ctypes.c_void_p
        lib.atl_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_long]
        lib.atl_num_samples.restype = ctypes.c_long
        lib.atl_num_samples.argtypes = [ctypes.c_void_p]
        lib.atl_num_tokens.restype = ctypes.c_long
        lib.atl_num_tokens.argtypes = [ctypes.c_void_p]
        lib.atl_close.argtypes = [ctypes.c_void_p]
        lib.atl_loader_new.restype = ctypes.c_void_p
        lib.atl_loader_new.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ]
        lib.atl_loader_batches_per_epoch.restype = ctypes.c_long
        lib.atl_loader_batches_per_epoch.argtypes = [ctypes.c_void_p]
        lib.atl_loader_start_epoch.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.atl_loader_next.restype = ctypes.c_int
        lib.atl_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)
        ]
        lib.atl_loader_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def is_available() -> bool:
    """True if the native library is built (or buildable) on this host."""
    return _load_library() is not None


def build_error() -> str | None:
    _load_library()
    return _build_error


_MASK64 = np.uint64(0xFFFFFFFFFFFFFFFF)
# largest corpus (in samples) for which the fallback reproduces the native
# shuffle bit-for-bit (the swap loop is Python-sequential, ~1s per 2M)
_EXACT_SHUFFLE_MAX = int(os.environ.get("ACCELERATE_TPU_EXACT_SHUFFLE_MAX",
                                        2_000_000))


def _splitmix64_draws(seed: int, epoch: int, n: int) -> np.ndarray:
    """The SplitMix64 stream token_loader.cpp uses, vectorized: draw k is
    mix(seed_epoch + (k+1)*GAMMA)."""
    gamma = np.uint64(0x9E3779B97F4A7C15)
    s0 = np.uint64((seed ^ (epoch * 0xD1B54A32D192ED03)) & 0xFFFFFFFFFFFFFFFF)
    with np.errstate(over="ignore"):
        z = (s0 + (np.arange(1, n + 1, dtype=np.uint64)) * gamma) & _MASK64
        z = ((z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _MASK64
        z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _MASK64
        return z ^ (z >> np.uint64(31))


def _epoch_order(num_samples: int, seed: int, epoch: int, shuffle: bool,
                 rank: int, world: int) -> np.ndarray:
    """The EXACT permutation+shard the C++ side computes (SplitMix64
    Fisher-Yates, wraparound stride shard): mixed native/fallback fleets
    therefore see bit-identical epoch orders and disjoint host shards."""
    idx = np.arange(num_samples, dtype=np.int64)
    if shuffle and num_samples > 1:
        if num_samples > _EXACT_SHUFFLE_MAX:
            # the bit-exact Fisher-Yates swap loop is Python-sequential;
            # above this size use numpy's C shuffle instead. Still
            # deterministic per (seed, epoch) — but a fleet MIXING native
            # and fallback hosts would see different permutations, so warn.
            import warnings

            warnings.warn(
                f"corpus has {num_samples} samples; fallback shuffle switches "
                "to numpy (not bit-identical to the native loader). Ensure "
                "all hosts use the same implementation, or set "
                "ACCELERATE_TPU_EXACT_SHUFFLE_MAX higher.",
                stacklevel=2,
            )
            rng = np.random.default_rng((seed ^ (epoch * 0xD1B54A32D192ED03)) & 0xFFFFFFFF)
            rng.shuffle(idx)
        else:
            draws = _splitmix64_draws(seed, epoch, num_samples - 1)
            for k, i in enumerate(range(num_samples - 1, 0, -1)):
                j = int(draws[k] % np.uint64(i + 1))
                idx[i], idx[j] = idx[j], idx[i]
    per = -(-num_samples // world)
    take = (rank + np.arange(per, dtype=np.int64) * world) % num_samples
    return idx[take]


class TokenCorpusLoader:
    """Iterate `{"input_ids": int32 [batch, sample_len]}` batches from a flat
    binary token file.

    Sized batch iterable — plugs straight into `Accelerator.prepare`/
    `prepare_data_loader`. Construct with `rank=state.process_index,
    world=state.num_processes`: the loader shards the corpus itself and sets
    `is_host_sharded`, which tells `prepare_data_loader` NOT to stride its
    batches across hosts a second time.

    Uses the C++ core when available, else the NumPy fallback.
    """

    def __init__(
        self,
        path: str,
        sample_len: int,
        batch_size: int,
        dtype: np.dtype | str = np.int32,
        shuffle: bool = True,
        seed: int = 0,
        rank: int = 0,
        world: int = 1,
        drop_last: bool = True,
        threads: int = 2,
        prefetch_depth: int = 4,
        force_fallback: bool = False,
    ) -> None:
        self.path = path
        self.sample_len = int(sample_len)
        self.batch_size = int(batch_size)
        self.dtype = np.dtype(dtype)
        if self.dtype not in _DTYPES:
            raise ValueError(f"dtype {self.dtype} not supported; use uint16/int32/uint32")
        self.shuffle = shuffle
        self.seed = int(seed)
        self.rank, self.world = int(rank), int(world)
        if self.world <= 0 or not (0 <= self.rank < self.world):
            raise ValueError(f"invalid shard rank={rank} world={world}")
        if self.batch_size <= 0 or self.sample_len <= 0:
            raise ValueError(
                f"batch_size/sample_len must be positive, got "
                f"{batch_size}/{sample_len}"
            )
        # downstream prepare() must not shard again: this loader already
        # yields only this host's shard
        self.is_host_sharded = self.world > 1
        self.drop_last = drop_last
        self.threads, self.prefetch_depth = threads, prefetch_depth
        self.epoch = 0

        self._lib = None if force_fallback else _load_library()
        self._corpus = None
        self._loader = None
        if self._lib is not None:
            self._corpus = self._lib.atl_open(
                path.encode(), _DTYPES[self.dtype], self.sample_len
            )
            if not self._corpus:
                raise FileNotFoundError(f"cannot mmap token file {path}")
            self.num_samples = self._lib.atl_num_samples(self._corpus)
            self._loader = self._lib.atl_loader_new(
                self._corpus, self.batch_size, int(shuffle), self.seed,
                self.rank, self.world, int(drop_last), threads, prefetch_depth,
            )
            if not self._loader:
                raise RuntimeError(
                    "native loader creation failed (args rejected by atl_loader_new)"
                )
        else:
            self._mm = np.memmap(path, dtype=self.dtype, mode="r")
            self.num_samples = len(self._mm) // self.sample_len
        per = -(-self.num_samples // self.world)
        self.num_batches = (
            per // self.batch_size if drop_last
            else -(-per // self.batch_size)
        )
        # drop_last=False wraps the final batch with recycled rows; report
        # them like every other loader so gather_for_metrics can drop them
        # (DataLoaderShard reads these at end of epoch). Only exact when the
        # host shards themselves are even (num_samples % world == 0) — with
        # uneven shards the wrapped rows are cross-host duplicates that the
        # uniform (hosts, batch, real) layout cannot identify.
        real_tail = per - self.batch_size * (self.num_batches - 1)
        if (not drop_last and 0 < real_tail < self.batch_size
                and self.num_samples % self.world == 0):
            self.remainder = real_tail * self.world
            self.tail_layout = (self.world, self.batch_size, real_tail)
        else:
            self.remainder = -1
            self.tail_layout = None

    @property
    def implementation(self) -> str:
        """"native" (the C++ core built from token_loader.cpp) or "numpy"."""
        return "native" if self._loader is not None else "numpy"

    def set_epoch(self, epoch: int) -> None:
        self.epoch = int(epoch)

    def __len__(self) -> int:
        return self.num_batches

    def __iter__(self):
        if self._loader is not None:
            yield from self._iter_native()
        else:
            yield from self._iter_fallback()
        self.epoch += 1

    def _iter_native(self):
        out = np.empty((self.batch_size, self.sample_len), np.int32)
        ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        self._lib.atl_loader_start_epoch(self._loader, self.epoch)
        while True:
            rc = self._lib.atl_loader_next(self._loader, ptr)
            if rc != 0:
                break
            yield {"input_ids": out.copy()}

    def _iter_fallback(self):
        order = _epoch_order(
            self.num_samples, self.seed, self.epoch, self.shuffle,
            self.rank, self.world,
        )
        L, B = self.sample_len, self.batch_size
        tokens = self._mm
        n = len(order)
        for b in range(self.num_batches):
            rows = [order[(b * B + i) % n] for i in range(B)]
            batch = np.stack(
                [np.asarray(tokens[r * L : (r + 1) * L], dtype=np.int32) for r in rows]
            )
            yield {"input_ids": batch}

    def close(self) -> None:
        if self._loader is not None:
            self._lib.atl_loader_free(self._loader)
            self._loader = None
        if self._corpus is not None:
            self._lib.atl_close(self._corpus)
            self._corpus = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


def write_token_file(path: str, tokens: np.ndarray) -> str:
    """Write a flat binary token file in a supported dtype."""
    arr = np.ascontiguousarray(tokens)
    if arr.dtype not in _DTYPES:
        arr = arr.astype(np.int32)
    arr.tofile(path)
    return path
