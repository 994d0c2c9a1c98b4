"""Front end + dispatch: host-clock time of the `run()` call until it
returns (the `bench.dispatch` span), mean per job in the window.  Covers
`prepare_env`, the salting probe, the signature lookup and the enqueue."""


def read(r):
    if not r.dispatch_s:
        return None
    return 1e3 * sum(r.dispatch_s) / len(r.dispatch_s)
