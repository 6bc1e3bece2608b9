"""Generated tables: made from the seed by the configuration's generator,
kept as Parquet under ``benchmarks/.cache/<config>/seed<n>/`` and read back
when present and whole."""

import json
import os
import shutil

import pyarrow.parquet as pq

from harness import spec

ROW_GROUP = 1 << 20


def ensure(cache_dir: str, generator: str, args: dict, seed: int) -> dict:
    """Return {table: path}; generate unless the manifest (written last)
    matches the files' row counts."""
    manifest = os.path.join(cache_dir, "manifest.json")
    try:
        with open(manifest, encoding="utf-8") as f:
            rows = json.load(f)["rows"]
        paths = {t: os.path.join(cache_dir, f"{t}.parquet") for t in rows}
        if all(pq.read_metadata(paths[t]).num_rows == n
               for t, n in rows.items()):
            return paths
    except (OSError, ValueError, KeyError):
        pass
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    tables = spec.load_module("datagen", generator).generate(seed, **args)
    paths = {}
    for name, table in tables.items():
        paths[name] = os.path.join(cache_dir, f"{name}.parquet")
        pq.write_table(table, paths[name], row_group_size=ROW_GROUP)
    with open(manifest, "w", encoding="utf-8") as f:
        json.dump({"generator": generator, "args": args, "seed": seed,
                   "rows": {t: tables[t].num_rows for t in tables}}, f)
    return paths


def for_cell(cell, seed: int, rehearse: bool) -> dict:
    """The cell's tables for this seed, at the configuration's size or, in a
    rehearsal, at its small one (kept apart in the cache)."""
    gen = cell.config["datagen"]
    cache_dir = os.path.join(
        spec.BENCH_DIR, ".cache",
        cell.config_name + ("-rehearsal" if rehearse else ""), f"seed{seed}")
    return ensure(cache_dir, gen["module"],
                  gen["rehearsal_args" if rehearse else "args"], seed)


def read_columns(paths: dict, table: str, columns):
    """Dates come as datetime64 (compared by numpy, not one by one)."""
    return pq.read_table(paths[table], columns=columns).to_pandas(
        date_as_object=False)


def row_count(paths: dict, table: str) -> int:
    return pq.read_metadata(paths[table]).num_rows
