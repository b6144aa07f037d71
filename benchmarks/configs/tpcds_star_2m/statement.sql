SELECT d_year, i_brand_id brand_id, i_brand brand,
       sum(ss_ext_sales_price) sum_agg
FROM store_sales
JOIN date_dim ON d_date_sk = ss_sold_date_sk
JOIN item ON ss_item_sk = i_item_sk
WHERE i_manufact_id = {manufact_id} AND d_moy = {moy}
GROUP BY d_year, i_brand_id, i_brand
ORDER BY d_year, sum_agg DESC, brand_id
LIMIT 100
