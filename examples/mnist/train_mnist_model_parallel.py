"""Model-parallel MNIST — analogue of the reference's model-parallel MNIST
example built on ``MultiNodeChainList`` (reference: ``examples/``; unverified
— mount empty, see SURVEY.md).

The MLP is split across TWO pipeline ranks: rank 0 owns the first half,
rank 1 the second; activations flow 0→1 by ``ppermute`` and gradients flow
back automatically (no ``pseudo_connect`` — see links/multi_node_chain_list
docstring).  Every other mesh device is a data-parallel replica: the mesh
is ``(pipe=2, data=world/2)``.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from train_mnist import make_dataset  # noqa: E402  (same dataset)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batchsize", type=int, default=128)
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--platform", default=None)
    args = p.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import jax

    from chainermn_tpu.utils import enable_compile_cache

    enable_compile_cache()      # before the first jit

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as cmn
    from chainermn_tpu.links import MultiNodeChainList
    from chainermn_tpu.models import (
        accuracy, init_mlp, mlp_apply, softmax_cross_entropy,
    )
    from chainermn_tpu.parallel import MeshConfig

    n_dev = len(jax.devices())
    if n_dev < 2:
        print(f"needs >=2 devices for pipe=2, have {n_dev} — exiting")
        return None
    mc = MeshConfig(pipe=2, data=n_dev // 2)
    print(f"mesh: {mc}")

    # two-stage MLP over the pipe axis (the MultiNodeChainList graph)
    mn = MultiNodeChainList(axis_name="pipe")
    mn.add_link(
        lambda k: init_mlp(k, [784, 256, 256]),
        mlp_apply, owner=0, rank_out=1, name="lower_half")
    mn.add_link(
        lambda k: init_mlp(k, [256, 10]),
        mlp_apply, owner=1, rank_in=0, name="upper_half")
    params = mn.init(jax.random.PRNGKey(0))

    train, test = make_dataset()
    xs = np.stack([x for x, _ in train])
    ys = np.stack([y for _, y in train])
    xt = np.stack([x for x, _ in test])
    yt = np.stack([y for _, y in test])

    opt = optax.sgd(args.lr)
    opt_state = opt.init(params)

    def sharded_step(params, x, y):
        def loss_of(ps):
            logits = mn.apply(ps, x)
            # batch is data-sharded → pmean over data; pipe-replicated loss
            return jax.lax.pmean(
                softmax_cross_entropy(logits, y), "data")

        loss, grads = jax.value_and_grad(loss_of)(params)
        grads = mn.reduce_grads(grads)   # keep replicas consistent
        return loss, grads

    grad_fn = jax.shard_map(
        sharded_step, mesh=mc.mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()))

    @jax.jit
    def step(params, opt_state, x, y):
        loss, grads = grad_fn(params, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def eval_logits(params, x):
        return jax.shard_map(
            lambda ps, xx: mn.apply(ps, xx),
            mesh=mc.mesh, in_specs=(P(), P("data")), out_specs=P("data"),
        )(params, x)

    dp = mc.axis_size("data")
    bs = max(args.batchsize // dp, 1) * dp   # divisible by the data axis
    n_eval = len(xt) // dp * dp
    n_batches = len(xs) // bs
    for epoch in range(args.epoch):
        perm = np.random.RandomState(epoch).permutation(len(xs))
        total = 0.0
        for i in range(n_batches):
            idx = perm[i * bs:(i + 1) * bs]
            params, opt_state, loss = step(
                params, opt_state, jnp.asarray(xs[idx]),
                jnp.asarray(ys[idx]))
            total += float(loss)
        logits = eval_logits(params, jnp.asarray(xt[:n_eval]))
        acc = float(accuracy(logits, jnp.asarray(yt[:n_eval])))
        print(f"epoch={epoch + 1}  main/loss={total / n_batches:.4f}  "
              f"validation/accuracy={acc:.4f}")
    return acc


if __name__ == "__main__":
    main()
