"""Model families. A configuration file names its family (``run.family``);
the module of that name here gives the harness what depends on the
architecture: the mapping from the published keys to the program's model
config, the weights from a seed, the lower-precision control, and the plain
reference. A new family comes in as a new module and is named by the
configurations that use it; nothing that is here is edited."""

import importlib


def load(config: dict):
    return importlib.import_module(f"benchmark.families.{config['run']['family']}")
