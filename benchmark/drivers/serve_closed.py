"""Driver for traffic of kind ``serve_closed``: a fixed number of clients,
each sending its next request when the last one completes, so that the slots
stay full through the window.

    serve_out_tok_s = output tokens delivered inside the window / its seconds
"""

from __future__ import annotations


class Clients:
    def __init__(self, requests: list, n_clients: int):
        self.todo, self.n = list(reversed(requests)), n_clients

    def start(self, now: float, ramp_s: float) -> None:
        pass

    def admit(self, sv, now: float) -> None:
        while len(sv.live) < self.n and self.todo:
            sv.submit(self.todo.pop(), now)


def run(ctx) -> dict:
    serving = ctx.load("drivers", "serving")
    t = ctx.traffic
    sv = serving.Serving(ctx)
    sv.warm_up()
    # enough requests for every client through ramp and window at the
    # shortest output a request can have
    n = t["clients"] * t["requests_per_client"]
    m = serving.run_window(ctx, sv, Clients(serving.make_requests(ctx, n),
                                            t["clients"]))
    return serving.finish(ctx, sv, m,
                          {"serve_out_tok_s": m["tokens"] / m["window_s"]})
