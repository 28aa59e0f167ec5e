"""Seconds of the program's ingest: ``build_csr``, the transfer of its
arrays to the device and, for the compressed layout, ``compress``; each
ended by ``block_until_ready``."""


def read(record):
    s = record["setup"]
    return s["build_s"] + s["transfer_s"] + s.get("compress_s", 0.0)
