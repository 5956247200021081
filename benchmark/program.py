"""The seam between the benchmark's own weights and the program's model:
the configuration's file names the program's builder and the plain
reference; weights made under the reference's names are laid into the
program's parameter tree by construction order, shape by shape."""
from __future__ import annotations

import importlib

import numpy as np


def load_reference(config):
    path = config["reference"]          # benchmark/reference/<name>.py
    return importlib.import_module(path[:-3].replace("/", "."))


def build_model(config):
    """The program's model of this configuration, from its own builder."""
    module, builder = config["program"]["builder"].split(":")
    kwargs = {k: config[v] for k, v in config["program"]["kwargs"].items()}
    return getattr(importlib.import_module(module), builder)(**kwargs)


def leaf_dicts(tree):
    """The {weight, bias} dicts of a module-params tree in construction
    order (children are keyed '0', '1', ...; own parameters under '~')."""
    own = tree.get("~")
    if own:
        yield own
    for key in sorted((k for k in tree if k != "~"), key=int):
        yield from leaf_dicts(tree[key])


def to_program_tree(template, ref_params, names):
    """The reference-named weights (``names``: construction order) in the
    shape of the program's tree."""
    named = iter([(n, ref_params[n]) for n in names])

    def fill(tree):
        out = {}
        own = tree.get("~")
        if own is not None:
            if own:
                name, leaf = next(named)
                for part in own:
                    if tuple(own[part].shape) != tuple(leaf[part].shape):
                        raise ValueError(
                            f"{name}/{part}: the program holds "
                            f"{own[part].shape}, the configuration "
                            f"{leaf[part].shape}")
                out["~"] = {part: leaf[part] for part in own}
            else:
                out["~"] = own
        for key in sorted((k for k in tree if k != "~"), key=int):
            out[key] = fill(tree[key])
        return out

    filled = fill(template)
    if next(named, None) is not None:
        raise ValueError("the configuration has layers the program lacks")
    return filled


def from_program_tree(tree, names):
    """The program's parameters under the reference's names (host)."""
    return {name: {k: np.asarray(v) for k, v in leaf.items()}
            for name, leaf in zip(names, leaf_dicts(tree))}
