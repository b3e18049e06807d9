"""One file per model family: bench/families/<name>.py holds the family's
weight maker (``params``), its reference decode step (``step``) and the
reference's empty decode state (``state``). A configuration file names
its family module under "reference"; a name with no file is an error."""
from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(spec: dict):
    name = spec.get("reference")
    if not (isinstance(name, str) and name.isidentifier()
            and os.path.isfile(os.path.join(HERE, f"{name}.py"))):
        raise ValueError(f"{spec.get('arch')}: no family module "
                         f"bench/families/{name}.py")
    return importlib.import_module(f"bench.families.{name}")
