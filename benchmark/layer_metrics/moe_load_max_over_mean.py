"""Expert layer: the fullest held expert's assignments over the held
experts' mean, the largest over the layers (the model's counter on the
``ad.run`` span); 1 is an even load."""
from benchmark.harness import model_scopes


def read(run):
    return model_scopes.run_argument_mean(run, "moe_load_max_over_mean")
