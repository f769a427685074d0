"""Generators, one file per schema: `generate(config, seed, out_dir,
rows=None) -> {table name: directory of parquet files}`."""


def file_rows(rows: int, files: int) -> list:
    """Rows of each file: as even as `rows` allows, the longer first."""
    base, extra = divmod(rows, files)
    return [base + (1 if i < extra else 0) for i in range(files)]
