"""The built index, cached on disk inside the checkout, as a served search
node loads a prebuilt index instead of building it.

`load_or_build(cfg, build)` returns the index for a configuration: from
`bench/.cache/index/<config>/<key>.zidx` when that file exists, else from
`build()`, which it then saves there.  The key is a digest of the whole
configuration (its data seed and index settings included), of every source
file of the program (`src/repro/**/*.py`) and of the benchmark's own data
draw (`bench/lib/corpus.py`), so a change to any of them builds anew.  A
configuration keeps one file: saving removes its older keys.

Format: the index pickled (protocol 5) with every array's bytes taken out
of band, then each array's bytes in zstd frames of at most `CHUNK` bytes,
compressed and decompressed on all cores.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import zstandard

BENCH = Path(__file__).resolve().parents[1]
PROGRAM = BENCH.parent / "src" / "repro"
CACHE = BENCH / ".cache" / "index"
MAGIC = b"bench-index-1\n"
CHUNK = 32 << 20
LEVEL = 3
_U64 = struct.Struct("<Q")


def key(cfg: dict) -> str:
    """Digest of the configuration, the program's source and the data
    draw."""
    h = hashlib.sha256()
    h.update(json.dumps(cfg, sort_keys=True).encode())
    h.update(f"{sys.version}|{np.__version__}".encode())
    for p in sorted(PROGRAM.rglob("*.py")) + [BENCH / "lib" / "corpus.py"]:
        h.update(p.relative_to(BENCH.parent).as_posix().encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:24]


def _workers() -> int:
    return max(1, min(16, os.cpu_count() or 1))


def save(index, path: Path) -> int:
    """Write `index` to `path` (through a temporary file beside it);
    returns the bytes written."""
    bufs: list = []
    meta = pickle.dumps(index, protocol=5, buffer_callback=bufs.append)
    views = [b.raw() for b in bufs]
    pieces = [(i, lo) for i, v in enumerate(views)
              for lo in range(0, max(len(v), 1), CHUNK)]

    def pack(piece):
        i, lo = piece
        return zstandard.ZstdCompressor(level=LEVEL).compress(
            views[i][lo:lo + CHUNK])

    tmp = path.with_suffix(".tmp")
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(tmp, "wb") as f, ThreadPoolExecutor(_workers()) as pool:
        f.write(MAGIC)
        f.write(_U64.pack(len(meta)))
        f.write(meta)
        f.write(_U64.pack(len(views)))
        for v in views:
            f.write(_U64.pack(len(v)))
        f.write(_U64.pack(len(pieces)))
        for (i, lo), blob in zip(pieces, pool.map(pack, pieces)):
            f.write(_U64.pack(i))
            f.write(_U64.pack(lo))
            f.write(_U64.pack(len(blob)))
            f.write(blob)
        size = f.tell()
    os.replace(tmp, path)
    return size


def load(path: Path):
    """The index saved at `path`; its arrays own writable memory."""
    with open(path, "rb") as f:
        if f.read(len(MAGIC)) != MAGIC:
            raise ValueError(f"{path} is not a cached index")

        def u64():
            return _U64.unpack(f.read(8))[0]

        meta = f.read(u64())
        outs = [bytearray(u64()) for _ in range(u64())]
        pieces = []
        for _ in range(u64()):
            i, lo, n = u64(), u64(), u64()
            pieces.append((i, lo, f.read(n)))

    def unpack(piece):
        i, lo, blob = piece
        data = zstandard.ZstdDecompressor().decompress(blob)
        memoryview(outs[i])[lo:lo + len(data)] = data

    with ThreadPoolExecutor(_workers()) as pool:
        list(pool.map(unpack, pieces))
    return pickle.loads(meta, buffers=outs)


def load_or_build(cfg: dict, build, log):
    """(index, how): the cached index of `cfg`, or `build()` saved."""
    import time
    path = CACHE / cfg["name"] / f"{key(cfg)}.zidx"
    if path.is_file():
        t0 = time.monotonic()
        index = load(path)
        log(f"set-up: index loaded from {cfg['name']}/{path.name} "
            f"({path.stat().st_size} bytes) in {time.monotonic() - t0:.3f} s")
        return index, "loaded"
    index = build()
    t0 = time.monotonic()
    for old in path.parent.glob("*.zidx"):
        old.unlink()
    size = save(index, path)
    log(f"set-up: index saved to {cfg['name']}/{path.name} "
        f"({size} bytes) in {time.monotonic() - t0:.3f} s")
    return index, "built"
