"""STAGE-AWAIT corpus: a stage clock (utils/stagetime.py) held across an
`await` bills other connections' work to this connection's stage, and
their stages nest into it — self times stop adding up to wall time.
The fixed form times the synchronous write only, as server/io.py
`_flush_out` does."""


class _Conn:
    def __init__(self, node, writer):
        self.node = node
        self.writer = writer
        self._stage = node.stages.stage

    async def reply_bad(self, out):
        with self.node.stages.stage("reply_write"):
            self.writer.write(out)
            await self.writer.drain()       # STAGE-AWAIT fires

    async def relay_bad(self, frames):
        with self._stage("intake"):
            async for f in frames:          # STAGE-AWAIT fires: an
                self.writer.write(f)        # implicit await per step

    async def reply_fixed(self, out):
        with self.node.stages.stage("reply_write"):
            self.writer.write(out)
        await self.writer.drain()           # outside the stage: clean

    async def nested_fixed(self, out):
        with self._stage("plan"):
            async def later():              # a nested def is its own
                await self.writer.drain()   # scope: not this stage's
            self.writer.write(out)
        await later()
