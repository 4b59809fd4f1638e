"""Edge-Africa scenario: the paper's Tables I/II link profiles end-to-end.

Federated training of the MNIST CNN over every link preset (continent
averages + urban/rural), comparing default vs paper-tuned TCP parameters
and classifying each environment into the paper's Table III regions.

  PYTHONPATH=src python examples/edge_africa.py
"""

from repro.chaos import ChaosSchedule
from repro.core import EdgeClient, FederatedServer, ServerConfig, fedavg, mnist_cnn_task
from repro.data import make_federated_mnist, synthetic_mnist
from repro.transport import DEFAULT, PROFILES, TUNED_EDGE, classify
from repro.utils.compile_cache import enable_compile_cache


def run(link, tcp, rounds=5):
    shards = make_federated_mnist(10, 150, seed=1, iid=False, alpha=0.5)  # non-IID!
    clients = [EdgeClient(i, dataset=s) for i, s in enumerate(shards)]
    server = FederatedServer(
        mnist_cnn_task(),
        clients,
        fedavg(min_fit=0.3),
        tcp=tcp,
        chaos=ChaosSchedule(link),
        config=ServerConfig(rounds=rounds, local_steps=3, seed=1),
        eval_data=synthetic_mnist(300, seed=5),
    )
    return server.run().summary()


if __name__ == "__main__":
    enable_compile_cache()
    print(f"{'profile':14s} {'region':11s} {'default_time':>13s} {'tuned_time':>11s} {'acc':>6s}")
    for name in ("global_avg", "europe", "n_america", "asia", "africa", "africa_urban", "africa_rural"):
        link = PROFILES[name]
        region = classify(DEFAULT, link)
        d = run(link, DEFAULT)
        t = run(link, TUNED_EDGE)
        dt = f"{d['total_time_s']:.0f}s" if d["completed_rounds"] else "FAIL"
        tt = f"{t['total_time_s']:.0f}s" if t["completed_rounds"] else "FAIL"
        print(f"{name:14s} {region:11s} {dt:>13s} {tt:>11s} {t['final_accuracy']:.3f}")
