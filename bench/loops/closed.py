"""closed: TPC-H's throughput test. Each stream runs in a thread of its own
and sends its next query once the last one answered, until the window
closes. Stream ``s`` sends its first query ``s * stagger_ms`` (a key of the
traffic file) into the window, so that the streams' first queries reach the
service in one order."""
import threading
import time


def run(client):
    stagger = client.traffic.get("stagger_ms", 0) / 1e3

    def stream(s: int) -> None:
        client.wait_for_start()
        time.sleep(s * stagger)
        for q in client.order(s):
            if time.perf_counter() >= client.t_end:
                return
            client.wait(client.submit(s, q))

    threads = [threading.Thread(target=stream, args=(s,), daemon=True,
                                name=f"bench-stream-{s}")
               for s in range(client.n_streams)]
    for t in threads:
        t.start()
    client.started(threads)
