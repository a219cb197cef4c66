"""A per-layer metric moves ONE end-to-end metric, so a quantity read in
cells whose end-to-end metrics differ stands in ``BENCHMARK.json`` under a
name for each (``step_fwd_ms`` moves ``train_tokens_per_s``,
``step_fwd_ms.moe`` moves ``train_tokens_per_s.moe``) and is read by one
reader: ``layer_metrics/<name>.<suffix>.py`` says

    read = reader_alias.same_as(__file__)

and takes ``read`` from ``layer_metrics/<name>.py`` beside it."""

import importlib.util
import os


def same_as(path):
    """``read`` of the reader whose file is ``path`` less its last
    dotted part: ``a.b.moe.py`` reads what ``a.b.py`` reads."""
    base = os.path.basename(path)[:-len(".py")].rpartition(".")[0]
    if not base:
        raise ValueError(f"{path} names no reader to stand for")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + base.replace(".", "_"),
        os.path.join(os.path.dirname(path), base + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
