from repro.gbdt.model import GBDTParams, empty_params, from_state_dict, to_state_dict
from repro.gbdt.train import (GBDTConfig, fit, fit_decision_tree, fit_linear,
                              fit_random_forest)
from repro.gbdt.infer import predict, predict_efficient

__all__ = [
    "GBDTParams", "GBDTConfig", "empty_params", "fit", "fit_decision_tree",
    "fit_linear", "fit_random_forest", "predict", "predict_efficient",
    "to_state_dict", "from_state_dict",
]
