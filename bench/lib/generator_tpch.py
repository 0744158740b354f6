"""Generator `tpch`: what a configuration with `"generator": "tpch"` gets —
its tables from the seed, the reference's answers, and the schema file that
`lib/work.py` reads widths from."""

from __future__ import annotations

from lib import reference, tpchgen

SCHEMA_FILE = "schema_tpch.json"


def generate(out_dir: str, config: dict, scale: float, seed: int,
             whole: bool = False) -> dict[str, int]:
    """The configuration's tables with the columns it lists; `whole` writes
    every column of those tables (the source's whole record)."""
    tables = {t: tpchgen.SCHEMA[t] for t in config["tables"]} if whole else config["tables"]
    return tpchgen.generate_tpch(out_dir, scale=scale, seed=seed,
                                 files_per_table=config["files_per_table"], tables=tables)


def answers(data_dir: str, config: dict, queries: list[str],
            precision: str = "float64") -> dict:
    return reference.answers(data_dir, config["tables"], queries, precision)


compare, worst = reference.compare, reference.worst
