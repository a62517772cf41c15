"""A reader of the index's TCB files, written from the format's
description (``hyperspace_tpu/storage/layout.py`` docstring) and sharing
no code with the program: raw little-endian column buffers, then a JSON
footer (columns with dtype, offset and byte count; ``bucket``), its
length as 8 bytes little-endian, and the magic ``TCB1``. The builds the
benchmark times finalize by merge (the default), so every index file
holds one bucket."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

MAGIC = b"TCB1"
DTYPES = {
    "bool": np.bool_, "int8": np.int8, "int16": np.int16, "int32": np.int32,
    "int64": np.int64, "uint8": np.uint8, "uint16": np.uint16,
    "uint32": np.uint32, "uint64": np.uint64, "float32": np.float32,
    "float64": np.float64, "date32": np.int32,
}


def read(path: Path, columns) -> tuple:
    """({column: array}, footer) of one TCB file."""
    raw = Path(path).read_bytes()
    if raw[-4:] != MAGIC:
        raise ValueError(f"{path}: not a TCB file")
    flen = int.from_bytes(raw[-12:-4], "little")
    footer = json.loads(raw[-12 - flen:-12])
    n = footer["numRows"]
    metas = {m["name"]: m for m in footer["columns"]}
    out = {}
    for c in columns:
        m = metas[c]
        buf = np.frombuffer(raw, dtype=np.uint8, count=m["nbytes"], offset=m["offset"])
        out[c] = buf.view(DTYPES[m["dtype"]])[:n]
    return out, footer


def bucket_of_file(footer: dict, path: Path) -> int:
    """The bucket a bucket file holds (``b<bucket>-<id>.tcb``)."""
    b = footer.get("bucket")
    return int(Path(path).name[1:6]) if b is None else int(b)
