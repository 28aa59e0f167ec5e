"""Set-up seconds: from process start until the first timed job can start
(generate, ingest, plan, compile or cache load, warm job)."""


def read(record):
    return record["setup"]["setup_s"]
