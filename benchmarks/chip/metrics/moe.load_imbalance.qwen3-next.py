"""Mean, over the window's train steps, of the assignments of the busiest
held expert of any layer over the mean per layer and held expert
(``StepMetrics.moe_train_load_max`` / ``moe_train_load_mean``). A program
without the counters reads nothing."""


def read(run):
    loads = [(getattr(s, "moe_train_load_max", None),
              getattr(s, "moe_train_load_mean", None)) for s in run.steps]
    if not loads or any(None in x or x[1] <= 0 for x in loads):
        return None
    return sum(m / mean for m, mean in loads) / len(loads)
