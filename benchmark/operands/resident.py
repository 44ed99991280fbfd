"""``{"name": "resident"}``: the configuration's stack, made on the device
in one jitted call from the seed and handed to ``bolt.array`` where it
lies."""

import numpy as np

import lattice
import reference


class Resident:
    loader_seconds = loader_bytes = ()      # no loader: nothing to tally

    def __init__(self, spec, config, mesh, seed):
        import jax
        import jax.numpy as jnp
        import bolt_tpu as bolt
        self.shape = (int(config["records"]),) + tuple(config["record_shape"])
        self.bits = int(config["bits"])
        self.seed = seed
        if np.dtype(config["dtype"]) != np.float32:
            raise ValueError("the lattice is float32; config says %r"
                             % (config["dtype"],))
        if list(config["key_axes"]) != [0]:
            raise ValueError("resident operands are keyed on axis 0")
        P = jax.sharding.PartitionSpec
        sharding = jax.sharding.NamedSharding(mesh, P(mesh.axis_names[0]))
        make = jax.jit(
            lambda a, b: lattice.device_values(self.shape, a, b, self.bits),
            out_shardings=sharding)
        a, b = lattice.constants(seed)
        self.data = make(jnp.uint32(a), jnp.uint32(b))
        self.data.block_until_ready()
        self.array = bolt.array(self.data, context=mesh, axis=(0,))
        self.nbytes = int(np.prod(self.shape, dtype=np.int64)) * 4

    def operand(self):
        return self.array

    def reference(self, man):
        return reference.ResidentReference(man, self.data, self.shape,
                                           self.bits, self.seed)


make = Resident
