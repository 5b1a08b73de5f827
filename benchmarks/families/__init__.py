"""Model families, one module each, named by a configuration file's
`"family"`: how the program builds the model from the file's keys, how its
parameters map onto the plain reference (harness/reference_<family>.py), and
which of the yardstick's FLOP functions count it. A cell kind (cells/) asks
the family for these and names no model itself."""

import importlib


def family_of(config: dict):
    return importlib.import_module(f"families.{config['family']}")
