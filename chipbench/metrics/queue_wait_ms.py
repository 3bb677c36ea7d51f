"""Mean wait of a query in the queue, submit to flush (ms): the program's
``repro_server_stage_seconds{stage="queue_wait"}`` over the window."""


def read(record):
    return record.stage_mean_ms("queue_wait")
