"""The system under test as the benchmark builds it from a configuration
file: the program's own block for the file's ``model_type`` (the repo
configuration named by ``repo_config``), at the published widths, depth
and dtypes the file states.  The mapping from the file's keys to the
program's fields is the block's (``bench/blocks/<model_type>.py``)."""
from __future__ import annotations

from harness import spec


def model_config(c: dict):
    """The program's ModelConfig for a configuration file: the repo
    configuration of the same block, given the file's sizes."""
    from repro.configs import get_config
    return spec.load_block(c["model_type"]).program(
        c, get_config(c["repo_config"]).model)
