"""Quickstart: Byzantine-robust federated learning with AFA in ~40 lines.

Trains the paper's DNN on a synthetic MNIST-like dataset with 10 clients,
3 of which are byzantine.  Watch AFA (a) hold test error at the clean level,
(b) estimate per-client reputation, and (c) block the byzantine clients.

  PYTHONPATH=src python examples/quickstart.py
"""

from repro.data import make_mnist_like
from repro.fed import ServerConfig, SimConfig, run

data = make_mnist_like(n_train=4000, n_test=1000)

sim = SimConfig(
    num_clients=10,
    bad_frac=0.3,            # 3 byzantine clients (paper setting)
    scenario="byzantine",    # w_t + N(0, 20^2 I) updates
    rounds=12,
    local_epochs=2,
    batch_size=200,
    hidden=(512, 256),       # the paper's 784x512x256x10 DNN
    dropout=False,
    seed=0,
)

server = ServerConfig(
    rule="afa",
    num_clients=10,
    alpha0=3.0, beta0=3.0,   # Beta prior on client quality
    xi0=2.0, delta_xi=0.5,   # Algorithm 1 threshold schedule
    delta_block=0.95,        # eq. (6) blocking threshold
)

# the one front door: repro.fed.run routes to the classification simulator
# (workload=None -> the paper DNN); pass seeds=... for a sweep, or a
# ClientWorkload for LLM fine-tuning — same call
res = run(None, sim, server, data=data)

print("per-round test error (%):", [f"{e:.2f}" for e in res.test_error])
print("bad clients:", res.bad_clients.tolist())
print("blocked at round:", res.blocked_round[res.bad_clients].tolist())
print(f"detection rate: {res.detection_rate:.0%}")
print(f"mean round time: {res.round_time*1e3:.1f} ms/round")
assert res.test_error[-1] < 5.0, "AFA should keep error near the clean level"
assert res.detection_rate == 1.0
print("OK — AFA stayed robust and blocked every byzantine client.")
