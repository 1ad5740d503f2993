"""Expert layer: assignments a step that land on the experts held here,
mean over the layers (the model's counter, which the runner writes on its
``ad.run`` span once the step has finished)."""
from benchmark.harness import model_scopes


def read(run):
    return model_scopes.run_argument_mean(run, "moe_rows_here")
