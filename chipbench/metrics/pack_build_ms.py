"""Mean host time to find or build a chunk's pack (ms): the program's
``repro_server_stage_seconds{stage="pack_build"}`` over the window."""


def read(record):
    return record.stage_mean_ms("pack_build")
