"""Command-line application: ``python -m lightgbm_tpu config=train.conf``.

Reference: ``src/main.cpp:13`` -> ``Application::Run`` (``application.h:78``)
dispatching on ``task`` in {train, predict, convert_model, refit, save_binary};
config files are ``key=value`` lines with ``#`` comments, command-line
``key=value`` args override the file (``Config::KV2Map`` precedence).
"""

from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from .basic import Booster, Dataset
from .config import Config
from .engine import train as train_fn
from .io.parser import load_data_file
from .utils.log import Log


def parse_cli_params(argv: List[str]) -> Dict[str, str]:
    params: Dict[str, str] = {}
    file_params: Dict[str, str] = {}
    for arg in argv:
        key, _, val = arg.partition("=")
        params[key.strip()] = val.strip()
    if "config" in params or "config_file" in params:
        path = params.pop("config", None) or params.pop("config_file")
        with open(path) as fh:
            for line in fh:
                line = line.split("#", 1)[0].strip()
                if not line or "=" not in line:
                    continue
                key, _, val = line.partition("=")
                file_params[key.strip()] = val.strip()
    # precedence: explicit CLI args > config file (reference config.cpp).
    merged = dict(file_params)
    merged.update(params)
    return merged


def run(argv: List[str]) -> int:
    params = parse_cli_params(argv)
    task = params.pop("task", "train")
    cfg = Config(dict(params))

    def _load(path, with_feature_names=False):
        """Text load with the config's column specs — every task must
        drop/extract the SAME in-data columns (train/valid/predict/refit)."""
        return load_data_file(path, cfg.label_column, cfg.header,
                              weight_column=cfg.weight_column,
                              group_column=cfg.group_column,
                              ignore_column=cfg.ignore_column,
                              with_feature_names=with_feature_names)
    if task in ("train", "save_binary"):
        # Distributed bootstrap (reference Application::Train ->
        # Network::Init from machines/machine_list_file): num_machines > 1
        # brings up the multi-process jax runtime; the data mesh then spans
        # every process's devices, so tree_learner=data/voting shard rows
        # across machines exactly like the reference's socket cluster.
        from .parallel.distributed import init_distributed, shutdown
        rank, world = init_distributed(cfg)
        if world > 1 and cfg.pre_partition:
            Log.warning(
                "pre_partition=true: the CLI loads the full data file on "
                "every rank (row placement is done by the device mesh); "
                "for true per-rank data use the library API — "
                "parallel.pre_partition.sync_bin_mappers + "
                "global_row_sharded (reference "
                "DatasetLoader::LoadFromFile(rank, num_machines))")
        data_path = params.pop("data", None)
        if not data_path:
            Log.fatal(f"task={task} requires data=<file>")
        from .dataset import is_binary_dataset_file
        if is_binary_dataset_file(data_path):
            ds = Dataset(data_path, params=params)
        elif cfg.two_round:
            if cfg.weight_column or cfg.group_column or cfg.ignore_column:
                Log.fatal(
                    "two_round does not support in-data weight/group/"
                    "ignore column specs; use <data>.weight/<data>.query "
                    "side files or two_round=false")
            # two-round streaming load (reference two_round=true): never
            # materializes the raw f64 matrix
            from .dataset import load_train_data_two_round
            td = load_train_data_two_round(data_path, cfg)
            ds = Dataset(np.zeros((0, td.num_features)), label=td.label,
                         params=params)
            ds._train_data = td
        else:
            X, y, w, g, names = _load(data_path, with_feature_names=True)
            from .io.parser import position_side_file
            ds = Dataset(X, label=y, weight=w, group=g, params=params,
                         position=position_side_file(data_path,
                                                     expected_rows=len(y)),
                         feature_name=names or "auto")
        if task == "save_binary" or cfg.save_binary:
            # reference application task=save_binary / save_binary=true:
            # write "<data>.bin" next to the input and, for the standalone
            # task, stop there.  One writer under distributed training —
            # every rank holds the identical dataset and a shared
            # filesystem path must not be raced.
            ds.construct(params)
            if rank == 0:
                out_bin = data_path + ".bin"
                ds.save_binary(out_bin)
                Log.info(f"Saved binary dataset to {out_bin}")
            if task == "save_binary":
                if world > 1:
                    shutdown()
                return 0
        valid_sets, valid_names = [], []
        valid = params.pop("valid", params.pop("valid_data", ""))
        for i, vp in enumerate(p for p in valid.split(",") if p):
            Xv, yv, wv, gv = _load(vp)
            valid_sets.append(Dataset(Xv, label=yv, weight=wv, group=gv,
                                      reference=ds, params=params))
            valid_names.append(f"valid_{i}")
        from .callback import log_evaluation
        init_model = cfg.input_model or None
        try:
            bst = train_fn(dict(params), ds,
                           num_boost_round=cfg.num_iterations,
                           valid_sets=valid_sets, valid_names=valid_names,
                           init_model=init_model,
                           callbacks=[log_evaluation(cfg.metric_freq)])
            if rank == 0:
                # every rank trains the identical replicated model; one
                # writer avoids racing on a shared filesystem path
                out = cfg.output_model or "LightGBM_model.txt"
                bst.save_model(out)
                Log.info(f"Finished training; model saved to {out}")
            else:
                Log.info(f"Finished training (rank {rank}/{world}; rank 0 "
                         "writes the model)")
        finally:
            if world > 1:
                shutdown()
        return 0
    if task == "predict":
        model_path = cfg.input_model or "LightGBM_model.txt"
        data_path = params.get("data")
        if not data_path:
            Log.fatal("task=predict requires data=<file>")
        bst = Booster(model_file=model_path)
        # predict data must drop the same in-data columns training dropped
        X, _, _, _ = _load(data_path)
        pred = bst.predict(
            X, raw_score=cfg.predict_raw_score,
            start_iteration=cfg.start_iteration_predict,
            num_iteration=(cfg.num_iteration_predict
                           if cfg.num_iteration_predict > 0 else None),
            pred_early_stop=cfg.pred_early_stop,
            pred_early_stop_freq=cfg.pred_early_stop_freq,
            pred_early_stop_margin=cfg.pred_early_stop_margin,
            predict_disable_shape_check=cfg.predict_disable_shape_check)
        out = params.get("output_result", "LightGBM_predict_result.txt")
        np.savetxt(out, np.atleast_2d(pred.T).T, fmt="%.9g")
        Log.info(f"Finished prediction; results saved to {out}")
        return 0
    if task == "convert_model":
        from .convert_model import convert_model_file
        model_path = cfg.input_model or "LightGBM_model.txt"
        out = params.get("convert_model", "gbdt_prediction.cpp")
        convert_model_file(model_path, out,
                           params.get("convert_model_language", "cpp"))
        Log.info(f"Finished converting model; code saved to {out}")
        return 0
    if task == "refit":
        # Reference application.cpp task=refit: load model, refit leaf values
        # on the provided data, save (keeps every tree's structure).
        model_path = cfg.input_model or "LightGBM_model.txt"
        data_path = params.get("data")
        if not data_path:
            Log.fatal("task=refit requires data=<file>")
        X, y, w, g = _load(data_path)
        new_bst = Booster(model_file=model_path).refit(
            X, y, decay_rate=cfg.refit_decay_rate, weight=w, group=g)
        out = cfg.output_model or "LightGBM_model.txt"
        new_bst.save_model(out)
        Log.info(f"Finished refit; model saved to {out}")
        return 0
    Log.fatal(f"unknown task {task}")
    return 1


def main() -> None:
    # the process entry point, not run(): callers of run() in a live
    # process keep whatever cache configuration that process has
    from .utils.jax_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(run(sys.argv[1:]))


if __name__ == "__main__":
    main()
