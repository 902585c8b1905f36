"""Several clients on one ``RRTOEdgeServer``: every round is one
``run_round`` with one decode call per client, in lockstep, so each
client's call lasts its whole round.  Replaying clients' calls run as one
``jax.vmap``-batched donated step through the ``ReplayBatcher``; a client
that begins a new request (a fresh cache upload) replays alone that
round."""
from __future__ import annotations

from repro.serving.engine import RRTOServedLM
from repro.serving.multitenant import RRTOEdgeServer

from bench.serving import Client, ServingDriver


class Driver(ServingDriver):
    annotation = "bench.round"

    def build(self) -> None:
        self.edge = RRTOEdgeServer()
        for cid in range(self.traffic.clients):
            lm = RRTOServedLM(self.cfg, bucket_len=self.bucket_len,
                              params=self.params, edge=self.edge,
                              client_id=f"c{cid}")
            lm.session.load()
            self.clients.append(Client(cid, lm, self.traffic))

    def step(self, clients, inputs):
        results = self.edge.run_round(
            {f"c{c.cid}": inputs[c.cid] for c in clients}
        )
        return {c.cid: results[f"c{c.cid}"] for c in clients}

    def counters(self):
        out = super().counters()
        b = self.edge.batcher
        out.update(vmap_batches=b.vmap_batches, vmap_compiles=b.vmap_compiles,
                   batched_replays=b.batched_replays,
                   solo_replays=b.solo_replays)
        return out

    def warm_check(self) -> None:
        b = self.edge.batcher
        widest = max(b.batch_sizes, default=0)
        if len(self.clients) > 1 and (b.vmap_batches == 0
                                      or widest < len(self.clients)):
            raise RuntimeError("warm-up ran no vmap-batched round of "
                               f"{len(self.clients)} clients")
        if b.solo_replays == 0:
            raise RuntimeError("warm-up ran no solo replay (fresh cache)")

    def close(self) -> None:
        super().close()
        self.edge = None
