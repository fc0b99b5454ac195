"""Modules that a configuration, a traffic mix or a metric brings as a file
of its own (metric readers, plain references, traffic generators), found
by the name in the data and loaded by path.

A name is a plain file name (letters, digits, ``_``, ``.``, ``-``): it
never leads out of its directory.  A module that is not there is an
error naming the path it was looked for at; nothing falls back to
another module.
"""

from __future__ import annotations

import importlib.util
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def path_of(directory: str, name: str) -> str:
    return os.path.join(directory, f"{name}.py")


def names(directory: str) -> set:
    """The modules a directory holds, by name."""
    if not os.path.isdir(directory):
        return set()
    return {f[:-3] for f in os.listdir(directory)
            if f.endswith(".py") and NAME.match(f[:-3])}


def load(directory: str, name: str, kind: str, error=FileNotFoundError):
    """Execute ``<directory>/<name>.py`` as a module of its own; ``error``
    (naming the path) when the name is not plain or the file is missing."""
    path = path_of(directory, name)
    if not isinstance(name, str) or not NAME.match(name) \
            or not os.path.isfile(path):
        raise error(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
