"""Data generators, one module per schema, found by the name a
configuration file gives under ``datagen``.

A generator module exposes ``make(tables, seed, scale) -> dict`` where
``tables`` maps a table name to its ``{"rows": n, "files": k}`` from the
configuration file, and the result maps each table name to
``(pyarrow.Table, columns to dictionary-encode)`` (``True``: all, the
writer's default, right for a small dimension).  ``write`` below
turns that into ``<root>/<table>/part-NNNN.parquet``.
"""

import importlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream per table; ``seed`` is any whole number
    from 0 to past 2**31."""
    return np.random.default_rng([int(seed), int(stream)])


def dict_strings(codes: np.ndarray, values) -> pa.Array:
    """A string column from small-integer codes into ``values`` without
    building one Python string per row.  ``write`` stores no arrow
    schema, so the file holds an ordinary string column."""
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32)), pa.array(list(values)))


def write(root: str, made: dict, tables: dict) -> dict:
    """Write every generated table as ``files`` parquet files of equal
    rows (the remainder in the last).  Key columns get dictionary pages
    and money columns PLAIN pages: left to itself the writer starts a
    dictionary for a money column and falls back to PLAIN mid-chunk
    once the dictionary overflows."""
    jobs = []
    for name, (table, dict_cols) in made.items():
        files = int(tables[name].get("files", 1))
        os.makedirs(os.path.join(root, name))
        per = table.num_rows // files
        for i in range(files):
            n = per if i < files - 1 else table.num_rows - per * (files - 1)
            jobs.append((name, i, table.slice(i * per, n), dict_cols))

    def one(job) -> tuple:
        name, i, part, dict_cols = job
        path = os.path.join(root, name, f"part-{i:04d}.parquet")
        papq.write_table(part, path, store_schema=False,
                         use_dictionary=True if dict_cols is True
                         else list(dict_cols))
        return name, os.path.getsize(path)

    info = {name: {"rows": made[name][0].num_rows,
                   "files": int(tables[name].get("files", 1)), "bytes": 0}
            for name in made}
    with ThreadPoolExecutor(max_workers=min(8, len(jobs))) as pool:
        for name, nbytes in pool.map(one, jobs):
            info[name]["bytes"] += nbytes
    return info


def generate(module: str, root: str, tables: dict, seed: int) -> dict:
    """Generate and write ``tables`` with the generator module
    ``benchmark/datagen/<module>.py``."""
    gen = importlib.import_module(f"datagen.{module}")
    return write(root, gen.make(tables, seed), tables)
