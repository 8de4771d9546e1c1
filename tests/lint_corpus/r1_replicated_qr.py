"""R1 pair: a batched QR whose operand batch reaches it replicated is
factored whole on every device (XLA partitions a batch-sharded QR by
itself, but nothing re-shards a replicated one); the fix is shard_map over
the batch axis so each device factors only its slice."""
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

B, NB = 512, 64          # (B, NB, NB) f32 batch: QR results ~8.4 MB/device


def make_bad(mesh):
    def fn(a):
        q, r = jnp.linalg.qr(a)
        return q.sum() + r.sum()

    specs = (jax.ShapeDtypeStruct((B, NB, NB), jnp.float32),)
    return fn, specs, dict(in_shardings=(NamedSharding(mesh, P()),))


def make_good(mesh):
    def qr_local(a):
        q, r = jnp.linalg.qr(a)
        return jax.lax.psum(q.sum() + r.sum(), "data")

    def fn(a):
        return jax.shard_map(qr_local, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(a)

    specs = (jax.ShapeDtypeStruct((B, NB, NB), jnp.float32),)
    return fn, specs, dict(in_shardings=(NamedSharding(mesh, P("data")),))
