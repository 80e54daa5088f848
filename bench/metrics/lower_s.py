"""Host seconds in ``step.lower(state)``: tracing the step, the rewrite
ladder's output lowered to StableHLO, Mosaic lowering of every kernel."""


def read(record):
    return record["host"]["lower_time"]
