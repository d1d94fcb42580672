"""Paper reproduction driver (§IV): 16 agents, ResNet-20, CIFAR-like task.

Reproduces the Table I / Fig. 1 / Fig. 2 experiment protocol: non-IID shards
(5-8 classes, per-agent sample budget), one local epoch per round, 3
consensus steps, N = 2K, across {ring, erdos_renyi, hypercube} x
{classical, drt}.  Real CIFAR-10 is unavailable offline; the synthetic
CIFAR-like task preserves the comparisons (DESIGN.md §7).

Defaults are CPU-budgeted (reduced width/samples/epochs); crank
--width 16 --min-samples 1500 --max-samples 2000 --epochs 60 --image-size 32
for the paper's full protocol on real hardware.

Run:  PYTHONPATH=src python examples/decentralized_cifar.py --epochs 8
"""
import argparse
import csv
import sys
import time

import jax
import jax.numpy as jnp

from repro.core import DecentralizedTrainer, TrainerConfig, make_topology
from repro.core.topology import PAPER_ER_SEED
from repro.data import CifarLike, CifarLikeConfig, agent_minibatches
from repro.models.resnet import init_resnet20, resnet20_accuracy, resnet20_loss
from repro.optim import adamw, momentum
from repro.utils.compile_cache import enable_compile_cache


def run_experiment(args, topology_name: str, algorithm: str, data, shards, test):
    K = args.agents
    if topology_name == "erdos_renyi":
        topo = make_topology("erdos_renyi", K, p=0.1, seed=PAPER_ER_SEED)
    else:
        topo = make_topology(topology_name, K)
    opt = adamw(args.lr) if args.optimizer == "adam" else momentum(args.lr, 0.9)
    tr = DecentralizedTrainer(
        lambda p, b, rng: resnet20_loss(p, b),
        lambda key: init_resnet20(key, width=args.width),
        opt,
        topo,
        TrainerConfig(algorithm=algorithm, consensus_steps=3, codec=args.codec),
    )
    st = tr.init(jax.random.key(0))
    epoch_fn = jax.jit(tr.epoch)
    history = []
    for e in range(args.epochs):
        b = agent_minibatches(shards, batch_size=args.batch, epoch_seed=e)
        batches = {"images": jnp.asarray(b["images"]), "labels": jnp.asarray(b["labels"])}
        st, m = epoch_fn(st, batches, jax.random.key(e))
        # evaluate agent 0 (all agents are statistically equivalent)
        p0 = jax.tree.map(lambda x: x[0], st.params)
        test_acc = float(resnet20_accuracy(p0, {"images": test[0], "labels": test[1]}))
        tr_imgs = jnp.asarray(shards[0][0][: len(test[1])])
        tr_labs = jnp.asarray(shards[0][1][: len(test[1])])
        train_acc = float(resnet20_accuracy(p0, {"images": tr_imgs, "labels": tr_labs}))
        history.append(
            dict(epoch=e, loss=float(m["loss"]), test_acc=test_acc, train_acc=train_acc,
                 gen_gap=train_acc - test_acc, disagreement=float(m["disagreement"])),
        )
    return topo.lambda2(), history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=16)
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--optimizer", default="adam", choices=["adam", "momentum"])
    ap.add_argument("--noise", type=float, default=0.1)
    ap.add_argument("--min-samples", type=int, default=256)
    ap.add_argument("--max-samples", type=int, default=320)
    ap.add_argument("--topologies", default="ring,erdos_renyi,hypercube")
    ap.add_argument(
        "--codec", default=None,
        help="wire codec for the consensus exchange: identity|bf16|f16|int8|"
             "topk[:frac] (default: exact f32 exchange)",
    )
    ap.add_argument("--out-csv", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.codec:
        from repro.comm import compression_ratio

        # allocation-free: the accounting works on ShapeDtypeStructs
        template = jax.eval_shape(
            lambda k: init_resnet20(k, width=args.width), jax.random.key(0)
        )
        print(f"consensus wire codec: {args.codec} "
              f"({compression_ratio(template, args.codec):.1f}x vs f32)")

    data = CifarLike(CifarLikeConfig(image_size=args.image_size, noise=args.noise, max_shift=0))
    shards = data.paper_partition(
        num_agents=args.agents, min_samples=args.min_samples,
        max_samples=args.max_samples, seed=1,
    )
    tx, ty = data.test_set(512)
    test = (jnp.asarray(tx), jnp.asarray(ty))

    rows = []
    print(f"{'topology':12s} {'lambda2':>8s} {'algorithm':>10s} {'test acc':>9s} "
          f"{'gen gap':>8s} {'disagree':>9s}  time")
    for topo_name in args.topologies.split(","):
        for algo in ("classical", "drt"):
            t0 = time.time()
            lam2, hist = run_experiment(args, topo_name, algo, data, shards, test)
            last = hist[-1]
            print(f"{topo_name:12s} {lam2:8.3f} {algo:>10s} {last['test_acc']:9.3f} "
                  f"{last['gen_gap']:8.3f} {last['disagreement']:9.2f}  {time.time()-t0:.0f}s",
                  flush=True)
            for h in hist:
                rows.append(dict(topology=topo_name, lambda2=lam2, algorithm=algo, **h))
    if args.out_csv:
        with open(args.out_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
        print(f"wrote {args.out_csv}")


if __name__ == "__main__":
    main()
