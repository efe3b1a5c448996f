"""Median over scans of the client's latency (sent to answered) minus the
server's ``wall_seconds``: framing, JSON codec, socket and the wait for a
pool worker, in ms."""

from bench import wire


def read(run):
    return wire.median_outside_server_ms(run, "scan")
