"""setup_s: seconds from process start to the window's start (host clock):
imports, weights made on the device, the server built and warmed up."""


def read(run, metric):
    return run.setup_s
