"""One client on its own ``RRTOServedLM`` (a private offload server): each
call is ``session.infer`` on the client's next decode input, the path
interception -> ``RRTOClient`` stateful replay -> ``OffloadServer`` replay
executable -> kernels, with no batcher."""
from __future__ import annotations

from repro.serving.engine import RRTOServedLM

from bench.serving import Client, ServingDriver


class Driver(ServingDriver):
    annotation = "bench.infer"

    def build(self) -> None:
        for cid in range(self.traffic.clients):
            lm = RRTOServedLM(self.cfg, bucket_len=self.bucket_len,
                              params=self.params)
            lm.session.load()
            self.clients.append(Client(cid, lm, self.traffic))

    def step(self, clients, inputs):
        return {c.cid: c.session.infer(*inputs[c.cid]) for c in clients}
