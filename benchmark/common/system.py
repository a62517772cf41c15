"""The system under test, set up from a configuration file: the TPC-H
tables it lists written as parquet source files, a session pinned to
what the configuration states, its covering indexes, and the appended
RF1 sets."""

from __future__ import annotations

from pathlib import Path

import numpy as np

from . import tpch


class Deployment:
    def __init__(self, config: dict, seed: int, workspace: Path):
        self.config = config
        self.seed = seed
        self.ws = workspace
        self.paths = {t: workspace / t for t in config["tables"]}
        self.tables = {t: [] for t in config["tables"]}  # pyarrow tables per source file set

    # -- data ----------------------------------------------------------------
    def generate(self) -> None:
        """Every table the configuration lists, from the seed."""
        made = tpch.tables(self.config["tables"], self.config["scale_factor"], self.seed)
        for name, table in made.items():
            tpch.write_files(table, self.paths[name], self.config["tables"][name]["files"])
            self.tables[name].append(table)

    def append(self) -> None:
        """The configuration's appended sets (TPC-H RF1), one new source
        file per set per table, after the indexes were built."""
        spec = self.config.get("append")
        if not spec:
            return
        sets = tpch.rf1_sets(self.config["scale_factor"], self.seed, spec["sets"])
        for s, (li, od) in enumerate(sets):
            for name, table in (("lineitem", li), ("orders", od)):
                tpch.write_files(table, self.paths[name], 1, prefix=f"rf1-{s:03d}")
                self.tables[name].append(table)

    def rows(self, table: str) -> int:
        return sum(t.num_rows for t in self.tables[table])

    def column(self, table: str, name: str) -> np.ndarray:
        return np.concatenate([t.column(name).to_numpy() for t in self.tables[table]])

    # -- the program ---------------------------------------------------------
    def open(self) -> None:
        from hyperspace_tpu import constants as C
        from hyperspace_tpu.config import HyperspaceConf
        from hyperspace_tpu.hyperspace import Hyperspace
        from hyperspace_tpu.session import HyperspaceSession

        conf = {k: str(v) for k, v in self.config["conf"].items()}
        conf[C.INDEX_SYSTEM_PATH] = str(self.ws / "indexes")
        self.session = HyperspaceSession(HyperspaceConf(conf))
        self.session.enable_hyperspace()
        self.hs = Hyperspace(self.session)

    def index_spec(self, name: str) -> dict:
        return next(ix for ix in self.config["indexes"] if ix["name"] == name)

    def create_index(self, spec: dict, name: str = None) -> None:
        from hyperspace_tpu.index.index_config import IndexConfig

        df = self.session.read.parquet(str(self.paths[spec["table"]]))
        self.hs.create_index(
            df, IndexConfig(name or spec["name"], spec["indexed"], spec["included"])
        )

    def build_indexes(self) -> None:
        for spec in self.config["indexes"]:
            self.create_index(spec)

    def index_files(self, name: str) -> list:
        """The data files of the newest version of index ``name``."""
        root = self.ws / "indexes" / name
        versions = sorted(root.glob("v__=*"), key=lambda p: int(p.name.split("=")[1]))
        return sorted(versions[-1].glob("*.tcb")) if versions else []

    def close(self) -> None:
        from hyperspace_tpu.exec.hbm_cache import hbm_cache

        hbm_cache.reset()
        self.session = self.hs = None
