"""Seconds from the harness's first statement to the first timed step:
state generation, the rewrite ladder, tuning, lowering, compilation and
the compared first steps (host clock)."""


def read(record):
    return record["host"]["setup_time"]
